"""The CLOES cascade as a serving pipeline (the paper's deployed system).

Stages 1..T are the jointly-trained linear classifiers, executed by the
fused Pallas scorer in one pass over the candidate matrix; per-stage
survivor counts come from the Eq-10 expected-count thresholds learned at
training time. An optional NEURAL FINAL STAGE — any of the 10 assigned
architectures with a scalar value head — re-scores only the items that
survive the linear cascade, exactly how the paper treats the expensive
"Deep & Wide" feature (Table 1, cost 0.84): a costly scorer that the
cascade shields from the bulk of the traffic.

CascadeServer is now a thin COMPATIBILITY SHIM over the streaming
serving.session.CascadeSession engine: submit() queues unboundedly and
serve() drains everything, exactly as before — new code should use
CascadeSession directly (deadlines, admission control, flush policy,
degraded modes). The two are bit-identical on the same request set.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cascade as C
from repro.core import losses as L
from repro.models import base as MB
from repro.models import zoo as Z
from repro.serving.batching import RankRequest, RankResponse, RequestBatcher
from repro.serving.session import (CascadeSession, DegradePolicy,
                                   ServingConfig, split_results)


# ---------------------------------------------------------------------------
# Neural final stage: zoo model + mean-pool value head over item "token"
# encodings. Item features are quantized into the model's vocab — a stand-in
# tokenizer (the real system embeds item text/ids; the *compute* is real).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NeuralScorer:
    cfg: MB.ModelConfig
    params: dict
    head: jax.Array              # (d_model,)
    tokens_per_item: int = 8

    @classmethod
    def create(cls, cfg: MB.ModelConfig, key: jax.Array,
               tokens_per_item: int = 8) -> "NeuralScorer":
        kp, kh = jax.random.split(key)
        params = MB.materialize(Z.templates(cfg), kp, dtype=jnp.float32)
        # small head: an untrained final stage should perturb, not
        # dominate, the calibrated cascade score
        head = 0.002 * jax.random.normal(kh, (cfg.d_model,))
        return cls(cfg=cfg, params=params, head=head,
                   tokens_per_item=tokens_per_item)

    def tokenize(self, feats: jax.Array) -> jax.Array:
        """(N, d_x) -> (N, tokens_per_item) int32 by feature quantization."""
        n, d = feats.shape
        t = self.tokens_per_item
        take = feats[:, :t] if d >= t else jnp.pad(feats, ((0, 0), (0, t - d)))
        quant = jnp.clip(((take + 4.0) / 8.0 * (self.cfg.vocab - 1)), 0,
                         self.cfg.vocab - 1)
        return quant.astype(jnp.int32)

    def score(self, feats: jax.Array) -> jax.Array:
        """(N, d_x) -> (N,) scalar relevance scores: mean-pooled final
        hidden state through the value head."""
        tokens = self.tokenize(feats)
        hidden = self._hidden(tokens)
        return hidden.mean(axis=1) @ self.head

    def _hidden(self, tokens: jax.Array) -> jax.Array:
        params = self.params
        x = jnp.take(params["embed"], tokens, axis=0)
        b, s, _ = x.shape
        positions = jnp.arange(s)[None, :].repeat(b, 0)
        from repro.models import layers as Lyr
        wins = jnp.asarray(Z.window_schedule(self.cfg))

        def body(x, xs):
            p, w = xs
            x, _ = Z._dense_block_fwd(p, self.cfg, x, positions, w)
            return x, None

        x, _ = jax.lax.scan(body, x, (params["blocks"], wins))
        return Lyr.rms_norm(x, params["final_norm"])


# ---------------------------------------------------------------------------
# The cascade server.
# ---------------------------------------------------------------------------

class CascadeServer:
    """Thin compatibility shim over serving.session.CascadeSession:
    unbounded queue, no deadlines, no degradation — submit() then serve()
    drains everything in submit order, exactly the pre-session API."""

    def __init__(self, params: C.Params, cfg: C.CascadeConfig,
                 lcfg: L.LossConfig | None = None,
                 neural_stage: NeuralScorer | None = None,
                 neural_cost: float = 0.84,
                 use_fused_kernel: bool | None = None,
                 fused: str | None = None,
                 batcher: RequestBatcher | None = None):
        # fused names a core.pipeline.PLANS entry directly ('filter' — the
        # fully fused kernel, 'score' — the batched scorer + XLA stage
        # chain, 'none' — the XLA reference path). use_fused_kernel is the
        # pre-registry bool API, deprecated for one release of aliasing;
        # an explicit fused= always takes precedence over the legacy bool.
        if use_fused_kernel is not None:
            warnings.warn(
                "CascadeServer(use_fused_kernel=...) is deprecated; pass "
                "fused='filter' (True) or fused='none' (False) — a "
                "core.pipeline.PLANS plan name — instead",
                DeprecationWarning, stacklevel=2)
            if fused is None:
                fused = "filter" if use_fused_kernel else "none"
        self.fused = fused if fused is not None else "filter"
        self.use_fused_kernel = self.fused == "filter"
        self.batcher = batcher if batcher is not None else RequestBatcher()
        self.session = CascadeSession(
            params, cfg, lcfg, neural_stage=neural_stage,
            scfg=ServingConfig(
                plan=self.fused,
                group_buckets=tuple(self.batcher.buckets),
                batch_groups=self.batcher.batch_groups,
                max_queue=None,                        # legacy: unbounded
                degrade=DegradePolicy(high_watermark=None),
                neural_cost=neural_cost))
        self.params = self.session.params
        self.cfg = cfg
        self.lcfg = self.session.lcfg
        self.neural = neural_stage
        self.neural_cost = neural_cost
        self._futures = []

    @property
    def _rank(self):
        """The session's jitted pipeline (compile-cache introspection)."""
        return self.session._rank

    def rank_batch(self, batch: dict) -> dict:
        """Run the jitted hard-cascade pipeline on a padded batch and fetch
        it: host views scores, survivors, lat and stage_counts."""
        return split_results(np.asarray(self.session.rank_batch(batch)),
                             self.cfg.n_stages)

    def warmup(self) -> list[tuple[int, int]]:
        """Pre-compile the pipeline for every batcher shape bucket."""
        return self.session.warmup()

    # -- request API ------------------------------------------------------

    def submit(self, req: RankRequest) -> None:
        self._futures.append(self.session.submit(req))

    def serve(self) -> list[RankResponse]:
        # The session flushes bucket by bucket (shape order, not submit
        # order); the futures list restores submit order before return.
        self.session.flush()
        futures, self._futures = self._futures, []
        return [f.result() for f in futures]
