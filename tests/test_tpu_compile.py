"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode never checks Mosaic's lowering rules (block tiling, the
primitives it implements, VMEM limits); the TPU compiler installed with
jaxlib does, and it needs only a topology description and shapes. These
tests compile with interpret=False at the shapes the system runs: CLOES
d_x = 24 and T = 3 (configs/cloes.py), serving batches of up to 32
groups in the 16/64/256/1024/4096 buckets (4096 is the filter's cap), and
training minibatches of 64 groups of 64 items. Nothing runs, so they say
nothing about results or time. Each kernel's custom call carries the
`name=` its pallas_call sets: the profiler shows that name, and the
benchmark's readers find the kernels by it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cascade as C
from repro.core import losses as L
from repro.core import pipeline as P
from repro.data import features as F
from repro.kernels import ops as K
from repro.kernels.cascade_filter.kernel import cascade_filter
from repro.kernels.cascade_loss.kernel import cascade_loss, cascade_loss_bwd
from repro.kernels.cascade_score.kernel import (cascade_score_batched,
                                                cascade_score_batched_bwd)
from repro.serving.batching import staging_width
from repro.serving.session import CascadeSession, ServingConfig

D_X, T = 24, 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except (RuntimeError, ValueError, ImportError) as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One v5e chip, with the persistent compilation cache off: a compile
    for a described chip is written to the cache but cannot be read back
    without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def kernel_names(text: str) -> set[str]:
    """The instruction names of the compiled text's Pallas kernels, less
    their '.<n>' suffix: what a device trace calls them."""
    return {re.sub(r"\.\d+$", "", m) for m in re.findall(
        r"%([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}


def _compile(fn, sharding, *shapes, names=()):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the Pallas kernel, not a ref
    assert kernel_names(text) == set(names)
    return text


@pytest.mark.parametrize("g", [16, 64, 256, 512, 1024, 4096])
def test_cascade_filter_compiles(one_chip, g):
    b = 32
    _compile(lambda *a: cascade_filter(*a, interpret=False), one_chip,
             (b, g, D_X), (T, D_X), (b, T), (b, g), (b,),
             names=["cascade_filter"])


@pytest.mark.parametrize("b,g", [(32, 256), (64, 64)])
def test_cascade_score_batched_fwd_bwd_compile(one_chip, b, g):
    _compile(lambda *a: cascade_score_batched(*a, interpret=False), one_chip,
             (b, g, D_X), (T, D_X), (b, T), names=["cascade_score_batched"])
    _compile(lambda *a: cascade_score_batched_bwd(*a, interpret=False),
             one_chip, (b, g, D_X), (T, D_X), (b, T), (b, g, T),
             names=["cascade_score_batched_bwd"])


def test_cascade_loss_fwd_bwd_compile(one_chip):
    b, g, dc = 64, 64, D_X + 4
    _compile(lambda *a: cascade_loss(*a, d_x=D_X, interpret=False), one_chip,
             (b, g, dc), (T, D_X), (b, T), names=["cascade_loss"])
    _compile(lambda *a: cascade_loss_bwd(*a, d_x=D_X, interpret=False),
             one_chip, (b, g, dc), (T, D_X), (b, T), (b,), (T,), (b, T),
             names=["cascade_loss_bwd"])


def test_cascade_loss_gradient_keeps_the_kernel_names(one_chip):
    """Under autodiff, as the trainer runs them, the two loss kernels
    keep their names (unnamed, they read jvp_jit_cascade_loss__ and
    transpose_jvp_jit_cascade_loss_bwd___)."""
    b, g, dc = 64, 64, D_X + 4

    def loss(xc, w_eff, zq, zq_pen):
        ll, cost_pp, cnt_pp = K.cascade_loss_fused(xc, w_eff, zq, zq_pen,
                                                   interpret=False)
        return ll.sum() + cost_pp.sum() + cnt_pp.sum()

    _compile(jax.value_and_grad(loss, argnums=(1, 2, 3)), one_chip,
             (b, g, dc), (T, D_X), (b, T), (b, T),
             names=["cascade_loss", "cascade_loss_bwd"])


def _compile_run_cascade(sharding, b, g):
    masks = F.default_stage_masks(T)
    cfg = C.CascadeConfig(T, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = jax.eval_shape(lambda: C.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), params)
    x, q, mask, m_q = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
                       for s in ((b, g, cfg.d_x), (b, cfg.d_q), (b, g), (b,))]
    text = jax.jit(lambda p, *batch: P.run_cascade(
        p, cfg, *batch, fused="filter", interpret=False)).lower(
            params, x, q, mask, m_q).compile().as_text()
    assert kernel_names(text) == {"cascade_filter"}


def test_run_cascade_filter_plan_compiles(one_chip):
    """The whole serving pipeline at the normal ladder's largest bucket
    and batch."""
    _compile_run_cascade(one_chip, 32, 256)


def test_run_cascade_filter_plan_compiles_at_4096(one_chip):
    """The whole serving pipeline at the wide ladder's largest bucket
    (cloes3_wide) and batch."""
    _compile_run_cascade(one_chip, 32, 4096)


@pytest.mark.parametrize("g", [256, 4096])
def test_serving_program_compiles_from_one_staging_buffer(one_chip, g,
                                                          monkeypatch):
    """The session's jit_cascade_rank, as a flush dispatches it: the
    params and ONE (32, W) staging buffer, unpacked on the chip, at each
    ladder's largest bucket. The session's pipeline asks the backend
    whether to interpret its kernels; steered to the compiled kernel."""
    monkeypatch.setattr(K, "_auto_interpret", lambda: False)
    masks = F.default_stage_masks(T)
    cfg = C.CascadeConfig(T, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0))
    ses = CascadeSession(params, cfg, L.LossConfig(),
                         scfg=ServingConfig(plan="filter",
                                            group_buckets=(16, g),
                                            batch_groups=32))
    pspec = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    buf = jax.ShapeDtypeStruct((32, staging_width(g, cfg.d_x, cfg.d_q)),
                               jnp.float32, sharding=one_chip)
    lowered = ses._rank.lower(pspec, buf)
    assert len(jax.tree_util.tree_leaves(lowered.args_info)) == \
        len(jax.tree_util.tree_leaves(params)) + 1
    assert kernel_names(lowered.compile().as_text()) == {"cascade_filter"}
