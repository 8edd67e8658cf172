"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.cascade_filter.kernel import cascade_filter
from repro.kernels.cascade_filter.ref import cascade_filter_ref, filter_chain


# ---------------------------------------------------------------------------
# cascade_score_batched
# ---------------------------------------------------------------------------

# bfloat16 rows exercise only the kernels' input up-cast on top of the f32
# math; they ride the full tier-1 run (slow), keeping the fast loop inside
# its 90 s budget (scripts/ci.sh enforces it — see ROADMAP).
_BF16 = pytest.param(jnp.bfloat16, marks=pytest.mark.slow)


def _assert_score_parity(x, w, zq, dtype):
    got = np.asarray(ops.cascade_score_batched(x, w, zq, interpret=True))
    want = np.asarray(ops.cascade_score_batched_ref(x, w, zq))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert got.shape == x.shape[:2] + w.shape[:1]


@pytest.mark.parametrize("n", [1, 7, 512, 1000, 2048])
@pytest.mark.parametrize("d,t", [(24, 3), (8, 1), (128, 8), (40, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, _BF16])
def test_cascade_score_sweep(n, d, t, dtype):
    """One query group of n items."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n * 131 + d), 3)
    x = jax.random.normal(k1, (n, d), dtype)
    w = (0.3 * jax.random.normal(k2, (t, d))).astype(dtype)
    zq = jax.random.normal(k3, (t,), dtype)
    _assert_score_parity(x[None], w, zq[None], dtype)


@pytest.mark.parametrize("n,d,t", [(1000, 24, 3), (512, 8, 1), (2048, 40, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, _BF16])
def test_cascade_score_multi_block_batch_sweep(n, d, t, dtype):
    """Four query groups of n items, each one to four item blocks long:
    the (batch, item-block) grid in both directions at once."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n + d), 3)
    x = jax.random.normal(k1, (4, n, d), dtype)
    w = (0.3 * jax.random.normal(k2, (t, d))).astype(dtype)
    zq = jax.random.normal(k3, (4, t), dtype)
    _assert_score_parity(x, w, zq, dtype)


def test_cascade_score_cumulative_structure():
    """Output column j is column j-1 plus a non-positive increment."""
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (256, 24))
    w = 0.3 * jax.random.normal(k, (4, 24))
    zq = jnp.zeros((4,))
    out = np.asarray(ops.cascade_score_batched(x[None], w, zq[None],
                                               interpret=True))[0]
    assert (np.diff(out, axis=1) <= 1e-6).all()


# ---------------------------------------------------------------------------
# cascade_filter (fused score+filter)
# ---------------------------------------------------------------------------

def _filter_case(b, g, d, t, dtype, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, g, d)), dtype)
    w = jnp.asarray(0.3 * rng.normal(size=(t, d)), dtype)
    zq = jnp.asarray(rng.normal(size=(b, t)), dtype)
    mask = jnp.asarray(rng.random((b, g)) < 0.85, jnp.float32)
    m_q = jnp.asarray(rng.integers(1, 4 * g + 2, b), jnp.float32)
    return x, w, zq, mask, m_q


def _assert_filter_parity(x, w, zq, mask, m_q, tol):
    got = cascade_filter(x, w, zq, mask, m_q, interpret=True)
    want = cascade_filter_ref(x, w, zq, mask, m_q)
    np.testing.assert_allclose(np.asarray(got["lp"]), np.asarray(want["lp"]),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got["expected_counts"]),
                               np.asarray(want["expected_counts"]),
                               rtol=tol, atol=tol)
    # the discrete outputs must be BIT-identical, ties included
    np.testing.assert_array_equal(np.asarray(got["n_keep"]),
                                  np.asarray(want["n_keep"]))
    np.testing.assert_array_equal(np.asarray(got["survivors"]),
                                  np.asarray(want["survivors"]))
    return got


@pytest.mark.parametrize("g", [1, 7,
                               pytest.param(48, marks=pytest.mark.slow),
                               pytest.param(130, marks=pytest.mark.slow),
                               pytest.param(256, marks=pytest.mark.slow),
                               600,
                               pytest.param(1024, marks=pytest.mark.slow),
                               pytest.param(4096, marks=pytest.mark.slow)])
@pytest.mark.parametrize("d,t", [(24, 3), (8, 1), (40, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, _BF16])
def test_cascade_filter_sweep(g, d, t, dtype):
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    _assert_filter_parity(*_filter_case(2, g, d, t, dtype, seed=g * 37 + d),
                          tol=tol)


def test_cascade_filter_tied_scores():
    """Duplicated items produce exact score ties; the kernel's stable
    rank must break them identically to the oracle's stable argsort."""
    x, w, zq, mask, m_q = _filter_case(3, 64, 24, 3, jnp.float32, seed=0)
    x = x.at[:, 1::2].set(x[:, ::2])           # every item has a twin
    mask = jnp.ones_like(mask)
    got = _assert_filter_parity(x, w, zq, mask, m_q, tol=1e-5)
    surv = np.asarray(got["survivors"])
    assert 0 < surv[..., -1].sum() < surv.shape[0] * surv.shape[1]


def test_cascade_filter_fully_masked_group():
    x, w, zq, mask, m_q = _filter_case(3, 32, 24, 3, jnp.float32, seed=1)
    mask = mask.at[1].set(0.0)
    got = _assert_filter_parity(x, w, zq, mask, m_q, tol=1e-5)
    assert np.asarray(got["survivors"])[1].sum() == 0


def test_cascade_filter_mq_exceeds_group():
    """m_q >> G: keep counts must clip at the group size, keeping all."""
    x, w, zq, mask, m_q = _filter_case(2, 16, 24, 2, jnp.float32, seed=2)
    mask = jnp.ones_like(mask)
    zq = jnp.full_like(zq, 8.0)                 # near-certain pass probs
    got = _assert_filter_parity(x, w, zq, mask, jnp.full_like(m_q, 1e6),
                                tol=1e-5)
    assert (np.asarray(got["n_keep"]) == 16).all()
    assert (np.asarray(got["survivors"])[..., -1] == 1).all()


def _edge_case(kind, g):
    """Two groups of g items in which the select meets one edge."""
    x, w, zq, mask, m_q = _filter_case(2, g, 24, 3, jnp.float32, seed=g + 5)
    rng = np.random.default_rng(g)
    if kind == "ties":
        # five distinct items, each repeated: every score is tied with
        # about a fifth of the group, so each cut falls inside a tie class
        protos = rng.normal(size=(5, 24))
        x = jnp.asarray(protos[rng.integers(0, 5, (2, g))], jnp.float32)
    elif kind == "keep_one":
        zq = jnp.full_like(zq, -30.0)           # pass probs ~1e-13
    elif kind == "keep_all_alive":
        zq = jnp.full_like(zq, 30.0)            # pass probs round to 1
    elif kind == "masked_group":
        mask = mask.at[1].set(0.0)
    elif kind == "mq_below_g":
        m_q = jnp.asarray(rng.integers(1, g // 2, 2), jnp.float32)
    elif kind == "zero_scores":
        # logits past ~104: log sigmoid is -0.0 in the reference's cumsum
        # and +0.0 in the kernel's masked-sum prefix; tied at the top
        zq = jnp.zeros_like(zq)
        x = x.at[:, ::3].set(jnp.asarray(np.full(24, 60.0), jnp.float32))
        w = jnp.abs(w)
    return x, w, zq, mask, m_q


WIDE = [600, pytest.param(1024, marks=pytest.mark.slow),
        pytest.param(4096, marks=pytest.mark.slow)]


@pytest.mark.parametrize("g", [200] + WIDE)
@pytest.mark.parametrize("kind", ["ties", "keep_one", "keep_all_alive",
                                  "masked_group", "mq_below_g",
                                  "zero_scores"])
def test_cascade_filter_select_edges(kind, g):
    """At each edge of the select, the counting select equals the stable
    double argsort bit for bit: with 8-bit digits (200 items) and 4-bit
    ones past the old 512-item cap."""
    x, w, zq, mask, m_q = _edge_case(kind, g)
    got = _assert_filter_parity(x, w, zq, mask, m_q, tol=1e-5)
    surv = np.asarray(got["survivors"])
    n_keep = np.asarray(got["n_keep"])
    alive = np.asarray(mask).sum(-1)
    if kind == "ties":
        lp = np.asarray(got["lp"])
        prev = np.asarray(mask)[..., None] * np.concatenate(
            [np.ones_like(surv[..., :1]), surv[..., :-1]], -1)
        inside = 0
        for b, j in np.ndindex(*n_keep.shape):
            kept = surv[b, :, j] > 0
            tau = lp[b, kept, j].min()
            at_tau = (prev[b, :, j] > 0) & (lp[b, :, j] == tau)
            inside += 0 < (kept & at_tau).sum() < at_tau.sum()
        assert inside > 0                       # a cut split a tie class
    elif kind == "keep_one":
        assert (n_keep == 1).all()
        assert (surv.sum(1) == 1).all()
    elif kind == "keep_all_alive":
        assert (n_keep == alive[:, None]).all()
        assert (surv.sum(1) == alive[:, None]).all()
    elif kind == "masked_group":
        assert surv[1].sum() == 0
    elif kind == "zero_scores":
        assert (np.asarray(got["lp"])[:, ::3] == 0.0).all()


@pytest.mark.parametrize("g", WIDE)
def test_select_orders_signed_zeros_as_equal(g):
    """A -0.0 / +0.0 pair at the cut is one tie class, as in the stable
    argsort: the lower index survives whichever sign it carries."""
    from repro.kernels.cascade_filter.kernel import _select
    rng = np.random.default_rng(g)
    s = -np.abs(rng.normal(size=(1, g))).astype(np.float32) - 1.0
    zeros = rng.choice(g, 6, replace=False)
    s[0, zeros] = np.where(np.arange(6) % 2, 0.0, -0.0)
    alive = rng.random((1, g)) < 0.9
    alive[0, zeros] = True
    idx = jnp.arange(g, dtype=jnp.int32)[None, :]
    for keep, bits in itertools.product((1, 3, 6, 7), (4, 8)):
        got = _select(jnp.asarray(s), jnp.asarray(alive),
                      jnp.full((1, 1), float(keep)), idx, bits)
        want = filter_chain(jnp.asarray(s)[..., None],
                            jnp.asarray(alive, jnp.float32),
                            jnp.full((1, 1), float(keep)))[..., 0]
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want))
        assert np.asarray(got)[0, np.sort(zeros)[:min(keep, 6)]].all()


def test_cascade_filter_chain_is_nested():
    """Stage j survivors are a subset of stage j-1 survivors."""
    x, w, zq, mask, m_q = _filter_case(4, 96, 24, 4, jnp.float32, seed=3)
    got = cascade_filter(x, w, zq, mask, m_q, interpret=True)
    surv = np.asarray(got["survivors"])
    assert (np.diff(surv, axis=-1) <= 0).all()
    assert (surv[..., 0] <= np.asarray(mask)).all()


# ---------------------------------------------------------------------------
# swa_decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,hd", [
    (1, 4, 4, 64),
    pytest.param(2, 8, 2, 64, marks=pytest.mark.slow),
    pytest.param(3, 8, 1, 128, marks=pytest.mark.slow),
    pytest.param(2, 16, 16, 128, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("s,cache_len,window", [
    (1024, 1000, ops.NO_WINDOW),
    pytest.param(1024, 511, 256, marks=pytest.mark.slow),
    pytest.param(2048, 2047, 1024, marks=pytest.mark.slow),
    pytest.param(512, 0, ops.NO_WINDOW, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32, _BF16])
def test_swa_decode_sweep(b, h, hkv, hd, s, cache_len, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(b * 7 + s), 3)
    q = jax.random.normal(k1, (b, h, hd), dtype)
    k = jax.random.normal(k2, (b, s, hkv, hd), dtype)
    v = jax.random.normal(k3, (b, s, hkv, hd), dtype)
    got = np.asarray(ops.swa_decode(q, k, v, cache_len, window=window,
                                    interpret=True), np.float32)
    want = np.asarray(ops.swa_decode_ref(q, k, v, cache_len, window), np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.slow           # cross-checks the engine path the sweep above
#                             already pins against the kernel reference
def test_swa_decode_matches_engine_reference():
    """The kernel agrees with the engine's decode_attention path."""
    from repro.models.layers import decode_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    b, h, hkv, hd, s = 2, 8, 4, 64, 1024
    cache_len = 700
    q = jax.random.normal(k1, (b, 1, h, hd))
    k = jax.random.normal(k2, (b, s, hkv, hd))
    v = jax.random.normal(k3, (b, s, hkv, hd))
    eng = decode_attention(q, k, v, q_offset=cache_len, valid_len=cache_len + 1)
    ker = ops.swa_decode(q[:, 0], k, v, cache_len, interpret=True)
    np.testing.assert_allclose(np.asarray(eng[:, 0]), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)


def test_swa_decode_window_excludes_old_positions():
    """With window=W, changing K/V outside the window must not change the
    output."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    b, h, hkv, hd, s, w = 1, 4, 4, 64, 1024, 128
    cache_len = 900
    q = jax.random.normal(k1, (b, h, hd))
    k = jax.random.normal(k2, (b, s, hkv, hd))
    v = jax.random.normal(k3, (b, s, hkv, hd))
    out1 = ops.swa_decode(q, k, v, cache_len, window=w, interpret=True)
    k2_ = k.at[:, :cache_len - w].set(99.0)
    v2_ = v.at[:, :cache_len - w].set(-99.0)
    out2 = ops.swa_decode(q, k2_, v2_, cache_len, window=w, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
