"""chip_smoke.py on the CPU at a tiny size, with its platform and kernel
checks stubbed, plus the serve launcher's refusal to hide errors.

On the CPU the ops dispatch to their XLA references, so the compare phase
here checks the smoke's own plumbing (which responses it compares, and
that it fails on a real difference), not the kernels: the kernels are
compared in interpret mode by test_kernels.py and friends, and compiled
for the chip by test_tpu_compile.py.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

from repro.core import baselines as B  # noqa: E402
from repro.core import cascade as C  # noqa: E402
from repro.core import trainer as T  # noqa: E402
from repro.data import features as F  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.serving.session import CascadeSession, ServingConfig  # noqa: E402

TINY = chip_smoke.Size(n_queries=40, epochs=2, batch_groups=8, n_requests=12)
BUCKETS = tuple(sorted(ServingConfig().group_buckets))


def test_smoke_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "platform=cpu" in out
    assert "no TPU" in err
    assert '"ok"' not in out


def test_smoke_phases_at_tiny_size(monkeypatch, capsys):
    dev = {"platform": "tpu", "kind": "stub", "count": 1}
    checked = []
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: dev)
    monkeypatch.setattr(chip_smoke, "check_kernel",
                        lambda text, what: checked.append(what))
    monkeypatch.setattr(chip_smoke, "SIZE", TINY)
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "(off)")
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": dev}
    assert checked == ["train step (fused L3 loss)",
                       "serve plan=filter pipeline (32, 256)",
                       "serve plan=score pipeline (32, 256)"]
    text = "\n".join(lines)
    for want in ("compare serve plan=filter", "compare serve plan=score",
                 "compare loss", "recompiles after warmup 0",
                 "(per bucket {16: 4, 64: 4, 256: 4})"):
        assert want in text


def _served():
    """Requests in every bucket, served by an untrained cascade."""
    log = chip_smoke.make_log(dataclasses.replace(TINY, n_queries=10), seed=1)
    reqs = chip_smoke.make_requests(log, dataclasses.replace(
        TINY, n_requests=3), 1, BUCKETS)
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(3), scale=0.3)
    ses = serve.build_session(params, cfg, max_queue=0)
    for r in reqs:
        ses.submit(r, now_ms=0.0)
    resps = {r.request_id: r for r in ses.flush(now_ms=0.0)}
    return params, cfg, reqs, resps


def test_compare_serving_fails_on_a_real_difference():
    params, cfg, reqs, resps = _served()
    out = chip_smoke.compare_serving(params, cfg, "filter", reqs, resps)
    assert out["max_lp_err"] <= chip_smoke.LP_ATOL
    rid = reqs[-1].request_id
    good = resps[rid]
    keep = np.flatnonzero(good.survivors)
    scores = good.scores.copy()
    scores[keep[0]] += 10 * chip_smoke.LP_ATOL
    resps[rid] = dataclasses.replace(good, scores=scores)
    with pytest.raises(chip_smoke.SmokeFailure, match="served scores"):
        chip_smoke.compare_serving(params, cfg, "filter", reqs, resps)
    surv = good.survivors.copy()
    surv[keep[0]] = False
    resps[rid] = dataclasses.replace(good, survivors=surv)
    with pytest.raises(chip_smoke.SmokeFailure, match="survivors differ"):
        chip_smoke.compare_serving(params, cfg, "filter", reqs, resps)


def test_near_ties_cover_the_cut_and_keep_count_flips():
    lp = np.array([[-1.0], [-2.0], [-2.00001], [-3.0]], np.float32)
    ref = {"lp": lp, "survivors": np.array([[1], [1], [0], [0]], np.float32),
           "n_keep": np.array([2.0]), "mask": np.ones(4, np.float32)}
    # the cut between ranks 2 and 3 is inside the tolerance
    assert chip_smoke._near_ties(ref, ref["n_keep"], 4).tolist() == [
        False, True, True, False]
    lp[2, 0] = -2.5          # a clear cut: nothing is near
    assert not chip_smoke._near_ties(ref, ref["n_keep"], 4).any()
    # the other path kept one more: the item ranked between the counts
    assert chip_smoke._near_ties(ref, np.array([3.0]), 4).tolist() == [
        False, False, True, False]


@pytest.mark.parametrize("faults", [[], ["--faults", "0.5"]])
def test_serve_exit_code_when_every_request_errors(monkeypatch, faults):
    """Without injected faults an error is a real failure and the
    launcher exits non-zero; the chaos legs keep exiting zero."""
    real_fit = B.fit_cloes
    monkeypatch.setattr(serve.B, "fit_cloes", lambda tr, **kw: real_fit(
        tr, lcfg=kw["lcfg"], tcfg=T.TrainConfig(epochs=0)))

    def broken(self, chunk):
        raise RuntimeError("device fault")

    monkeypatch.setattr(CascadeSession, "_execute_attempt", broken)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve", "--requests", "4",
                                      "--plan", "none", *faults])
    if faults:
        serve.main()
    else:
        with pytest.raises(SystemExit) as e:
            serve.main()
        assert "no faults injected" in str(e.value.code)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's own and the helper
    sets no other directory; unset, the cache goes to <checkout>/.jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache
    was_dir = jax.config.jax_compilation_cache_dir
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        if env_set:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was_dir
        else:
            assert path == str(Path(__file__).resolve().parents[1]
                               / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", was_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was_min)
        compilation_cache.reset_cache()
