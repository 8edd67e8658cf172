"""Checkpoint-layer tests: exact pytree round-trip (the pre-fix format
collapsed lists/tuples into string-keyed dicts, promoted Python scalars
to 0-d arrays, and silently degraded bf16 to raw void bytes), the crash-
safe commit protocol (atomic-rename crash window, checksum rejection of
bit flips, manifest-last ordering), numbered-step retention GC,
last-good fallback under seeded filesystem faults, bit-identical
kill-and-resume training, and the serving warmup-manifest round trip
(warm restart = zero recompiles)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.checkpoint.io as CKIO
from repro.checkpoint import (CheckpointCorrupt, CheckpointStore,
                              load_pytree, save_pytree)
from repro.core import baselines as B
from repro.core import cascade as C
from repro.core import losses as L
from repro.core import trainer as T
from repro.data import features as F
from repro.data import LogConfig, generate_log
from repro.serving.batching import alloc_batch
from repro.serving.faults import FsFaultConfig, FsFaultInjector
from repro.serving.session import CascadeSession, ServingConfig


# ---------------------------------------------------------------------------
# Exact round trip — the satellite regression. Each of these assertions
# FAILED on the pre-PR flat-namespace format.
# ---------------------------------------------------------------------------

def test_roundtrip_preserves_structure_and_scalars(tmp_path):
    tree = {
        "list": [1, 2.5, "s", None, True],
        "tup": (np.arange(3, dtype=np.float32), {"k": 7}),
        "nested": {"empty_list": [], "empty_dict": {}},
        "scalar": 3,
    }
    save_pytree(tmp_path / "ck", tree)
    out = load_pytree(tmp_path / "ck")
    # lists stay lists (NOT dicts keyed by "0", "1", ...)
    assert isinstance(out["list"], list)
    assert out["list"] == [1, 2.5, "s", None, True]
    # tuples stay tuples
    assert isinstance(out["tup"], tuple)
    assert isinstance(out["tup"][1], dict) and out["tup"][1]["k"] == 7
    # Python scalars stay Python scalars (NOT 0-d arrays)
    assert type(out["scalar"]) is int and out["scalar"] == 3
    assert type(out["list"][4]) is bool
    assert out["nested"] == {"empty_list": [], "empty_dict": {}}
    np.testing.assert_array_equal(out["tup"][0],
                                  np.arange(3, dtype=np.float32))


def test_roundtrip_dtypes_exact(tmp_path):
    tree = {
        "f32": np.linspace(0, 1, 7, dtype=np.float32),
        "f64": np.linspace(0, 1, 5, dtype=np.float64),
        "i32": np.arange(4, dtype=np.int32),
        "bf16": jnp.asarray([1.5, -2.25, 3.0], jnp.bfloat16),
        "zero_d": np.float32(2.5),
        "jax_key": jax.random.PRNGKey(3),
    }
    save_pytree(tmp_path / "ck", tree)
    out = load_pytree(tmp_path / "ck")
    assert out["f32"].dtype == np.float32
    assert out["f64"].dtype == np.float64
    assert out["i32"].dtype == np.int32
    # bf16 comes back as bf16 with the exact bit patterns (np.savez alone
    # degrades it to raw |V2 bytes)
    assert out["bf16"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        out["bf16"].view(np.uint16),
        np.asarray(jax.device_get(tree["bf16"])).view(np.uint16))
    assert out["zero_d"].shape == () and float(out["zero_d"]) == 2.5
    np.testing.assert_array_equal(out["jax_key"],
                                  np.asarray(tree["jax_key"]))


def test_noncontiguous_and_rejected_leaves(tmp_path):
    arr = np.arange(12).reshape(3, 4)[:, ::2]          # strided view
    save_pytree(tmp_path / "ck", {"a": arr})
    np.testing.assert_array_equal(load_pytree(tmp_path / "ck")["a"], arr)
    with pytest.raises(TypeError, match="keys must be strings"):
        save_pytree(tmp_path / "bad", {1: np.zeros(2)})
    with pytest.raises(TypeError, match="unsupported checkpoint leaf"):
        save_pytree(tmp_path / "bad", {"f": object()})


# ---------------------------------------------------------------------------
# Crash-safe commit protocol.
# ---------------------------------------------------------------------------

def test_crash_in_rename_window_leaves_last_good(tmp_path, monkeypatch):
    store = CheckpointStore(tmp_path, keep=3)
    store.save(1, {"w": np.full(4, 1.0)}, meta={"epoch": 1})

    # crash between the temp-file write and the rename: os.replace never
    # happens, so step 2 is never committed and step 1 stays intact
    def boom(src, dst):
        raise OSError("simulated crash before rename")
    monkeypatch.setattr(CKIO.os, "replace", boom)
    with pytest.raises(OSError, match="simulated crash"):
        store.save(2, {"w": np.full(4, 2.0)}, meta={"epoch": 2})
    monkeypatch.undo()

    store2 = CheckpointStore(tmp_path, keep=3)
    assert store2.steps() == [1]
    step, tree, meta = store2.load_latest()
    assert step == 1 and meta == {"epoch": 1}
    np.testing.assert_array_equal(tree["w"], np.full(4, 1.0))
    # stale temp files from the crashed writer are GC'd on the next save
    assert list(tmp_path.glob("*.tmp.*"))
    store2.save(3, {"w": np.full(4, 3.0)})
    assert not list(tmp_path.glob("*.tmp.*"))


def test_manifest_is_the_commit_point(tmp_path):
    store = CheckpointStore(tmp_path, keep=3)
    store.save(1, {"w": np.ones(3)})
    # arrays file present but manifest missing = never committed: not a
    # step, and loading it is FileNotFoundError, not a torn read
    base = tmp_path / "step_00000002"
    (tmp_path / "step_00000002.npz").write_bytes(b"orphan arrays")
    assert store.steps() == [1]
    with pytest.raises(FileNotFoundError):
        load_pytree(base)
    # manifest present but arrays missing IS a torn checkpoint
    (tmp_path / "step_00000001.npz").unlink()
    with pytest.raises(CheckpointCorrupt, match="torn checkpoint"):
        load_pytree(tmp_path / "step_00000001")


def test_checksum_rejects_bitflip_and_load_latest_falls_back(tmp_path):
    store = CheckpointStore(tmp_path, keep=3)
    store.save(1, {"w": np.full(8, 1.0)}, meta={"epoch": 1})
    store.save(2, {"w": np.full(8, 2.0)}, meta={"epoch": 2})
    # flip one byte of step 2's arrays file on disk
    p = tmp_path / "step_00000002.npz"
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0x01
    p.write_bytes(bytes(raw))

    with pytest.raises(CheckpointCorrupt):
        store.load(2)
    step, tree, meta = store.load_latest()      # falls back past step 2
    assert step == 1 and meta == {"epoch": 1}
    np.testing.assert_array_equal(tree["w"], np.full(8, 1.0))
    assert store.errors and store.errors[0][0] == 2


def test_truncated_arrays_file_detected(tmp_path):
    save_pytree(tmp_path / "ck", {"w": np.arange(64, dtype=np.float64)})
    p = tmp_path / "ck.npz"
    p.write_bytes(p.read_bytes()[:-20])
    with pytest.raises(CheckpointCorrupt, match="truncated"):
        load_pytree(tmp_path / "ck")


def test_crc_catches_flip_npz_cannot(tmp_path):
    """A bit flip in array DATA that we repair npz's own member-crc for:
    only the manifest's per-array checksum stands between it and a
    silently-wrong load."""
    save_pytree(tmp_path / "ck", {"w": np.zeros(4, np.uint8)})
    man = json.loads((tmp_path / "ck.json").read_text())
    # forge: rewrite the npz so its internal crc matches flipped data,
    # keeping total length identical (defeats the length check too)
    flipped = np.array([1, 0, 0, 0], np.uint8)
    import io as _io
    buf = _io.BytesIO()
    np.savez(buf, a0=flipped)
    forged = buf.getvalue()
    assert len(forged) == man["npz_bytes"]
    (tmp_path / "ck.npz").write_bytes(forged)
    with pytest.raises(CheckpointCorrupt, match="checksum"):
        load_pytree(tmp_path / "ck")


def test_retention_gc_keeps_exactly_n(tmp_path):
    store = CheckpointStore(tmp_path, keep=2)
    for s in range(1, 6):
        store.save(s, {"w": np.full(2, float(s))})
    assert store.steps() == [4, 5]
    # exactly keep*2 files remain (npz + json per step)
    assert len(list(tmp_path.iterdir())) == 4
    assert store.latest_step() == 5
    step, tree, _ = store.load_latest()
    assert step == 5
    np.testing.assert_array_equal(tree["w"], np.full(2, 5.0))
    with pytest.raises(ValueError, match="keep must be >= 1"):
        CheckpointStore(tmp_path, keep=0)


# ---------------------------------------------------------------------------
# Seeded filesystem chaos: correct-or-fallback, never silently wrong.
# ---------------------------------------------------------------------------

def test_fs_fault_injector_discipline():
    inj = FsFaultInjector(FsFaultConfig(torn_write_rate=0.5,
                                        truncate_rate=0.25,
                                        bitflip_rate=0.25, seed=3))
    payload = bytes(range(256))
    outs = [inj.on_write("p", payload) for _ in range(50)]
    torn = [o for o in outs if len(o) < len(payload)]
    assert torn and all(payload.startswith(o) for o in torn)  # strict prefix
    # disabled injector is a byte-identical no-op
    inj.enabled = False
    assert inj.on_read("p", payload) == payload
    inj.enabled = True
    stats = inj.snapshot()
    assert stats["torn_write"] == len(torn)
    # same seed -> same fault sequence (replayable chaos)
    inj2 = FsFaultInjector(FsFaultConfig(torn_write_rate=0.5,
                                         truncate_rate=0.25,
                                         bitflip_rate=0.25, seed=3))
    assert [inj2.on_write("p", payload) for _ in range(50)] == outs


def test_store_under_torn_write_chaos_never_silently_wrong(tmp_path):
    inj = FsFaultInjector(FsFaultConfig(torn_write_rate=0.4, seed=7))
    store = CheckpointStore(tmp_path / "chaos", keep=10, fs_faults=inj)
    for s in range(1, 16):
        store.save(s, {"w": np.full(4, float(s))}, meta={"s": s})
    inj.enabled = False                 # read back with clean IO
    assert inj.snapshot()["torn_write"] > 0
    res = store.load_latest()
    assert res is not None              # at least one save survived
    step, tree, meta = res
    # THE property: whatever load_latest returns is the checkpoint that
    # step actually committed — torn steps were skipped, not misread
    np.testing.assert_array_equal(tree["w"], np.full(4, float(step)))
    assert meta == {"s": step}


def test_store_under_read_chaos_never_silently_wrong(tmp_path):
    store = CheckpointStore(tmp_path / "c2", keep=10)
    for s in range(1, 6):
        store.save(s, {"w": np.full(4, float(s))}, meta={"s": s})
    inj = FsFaultInjector(FsFaultConfig(truncate_rate=0.3, bitflip_rate=0.3,
                                        seed=11))
    reader = CheckpointStore(tmp_path / "c2", keep=10, fs_faults=inj)
    for _ in range(10):
        reader.errors.clear()
        res = reader.load_latest()
        if res is None:
            continue                    # every step faulted this pass: fine
        step, tree, meta = res
        np.testing.assert_array_equal(tree["w"], np.full(4, float(step)))
        assert meta == {"s": step}


# ---------------------------------------------------------------------------
# Training resume: bit-identical kill-and-resume trajectory.
# ---------------------------------------------------------------------------

def _tiny_fit(tmp_path=None, *, epochs, resume=False, tcfg_kw=None,
              losses=None, **fit_kw):
    log = generate_log(LogConfig(n_queries=120, items_per_query=16, seed=5))
    tcfg = T.TrainConfig(loss="l3", epochs=epochs, batch_groups=8,
                         seed=3, **(tcfg_kw or {}))
    cb = (lambda step, loss: losses.append((step, loss))) \
        if losses is not None else None
    return B.fit_cloes(log, tcfg=tcfg, callback=cb,
                       checkpoint_dir=None if tmp_path is None else
                       str(tmp_path),
                       resume=resume, **fit_kw)


@pytest.mark.slow       # cross-engine trainer integration: 3 full fits
def test_resume_is_bit_identical(tmp_path):
    base_losses: list = []
    params_full, _ = _tiny_fit(epochs=4, losses=base_losses,
                               tcfg_kw={"log_every": 1})
    # interrupted run: checkpoint every epoch, stop after 2 (simulated
    # kill: just train 2 epochs with the checkpoint dir)
    _tiny_fit(tmp_path, epochs=2, tcfg_kw={"checkpoint_every": 1})
    # resumed run continues to 4
    resumed_losses: list = []
    info: dict = {}
    params_res, _ = _tiny_fit(tmp_path, epochs=4, resume=True,
                              losses=resumed_losses,
                              tcfg_kw={"checkpoint_every": 1,
                                       "log_every": 1},
                              train_info=info)
    assert info["restored_epoch"] == 2 and info["epochs_run"] == 2
    # params: BIT-identical
    for k in params_full:
        np.testing.assert_array_equal(np.asarray(params_full[k]),
                                      np.asarray(params_res[k]), strict=True)
    # loss trajectory: the resumed run's epochs 3-4 equal the full run's
    base = dict(base_losses)
    for step, loss in resumed_losses:
        assert base[step] == loss       # float equality, on purpose


@pytest.mark.slow       # trainer integration: two fits + corrupt fallback
def test_resume_falls_back_past_corrupt_newest(tmp_path):
    _tiny_fit(tmp_path, epochs=3, tcfg_kw={"checkpoint_every": 1})
    newest = sorted(tmp_path.glob("step_*.npz"))[-1]
    newest.write_bytes(newest.read_bytes()[:-40])    # torn: length mismatch
    info: dict = {}
    _tiny_fit(tmp_path, epochs=4, resume=True,
              tcfg_kw={"checkpoint_every": 1}, train_info=info)
    assert info["restored_epoch"] == 2  # fell back from the torn epoch 3


def test_resume_rejects_config_mismatch(tmp_path):
    _tiny_fit(tmp_path, epochs=2, tcfg_kw={"checkpoint_every": 1})
    with pytest.raises(ValueError, match="different training config"):
        _tiny_fit(tmp_path, epochs=4, resume=True,
                  tcfg_kw={"checkpoint_every": 1, "lr": 0.123})


def test_loop_engine_rejects_checkpointing(tmp_path):
    with pytest.raises(ValueError, match="scan-engine feature"):
        _tiny_fit(tmp_path, epochs=1, tcfg_kw={"engine": "loop"})


def test_resume_past_end_returns_restored_params(tmp_path):
    params_a, _ = _tiny_fit(tmp_path, epochs=2,
                            tcfg_kw={"checkpoint_every": 1})
    info: dict = {}
    params_b, _ = _tiny_fit(tmp_path, epochs=2, resume=True,
                            tcfg_kw={"checkpoint_every": 1},
                            train_info=info)
    assert info["epochs_run"] == 0
    for k in params_a:
        np.testing.assert_array_equal(np.asarray(params_a[k]),
                                      np.asarray(params_b[k]))


# ---------------------------------------------------------------------------
# Serving warm restart: manifest round trip, zero recompiles.
# ---------------------------------------------------------------------------

def _serving_session(params, cfg):
    return CascadeSession(params, cfg, L.LossConfig(),
                          scfg=ServingConfig(plan="filter",
                                             group_buckets=(8,),
                                             batch_groups=2))


def test_warm_restart_replays_manifest_with_zero_new_compiles(tmp_path):
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    ses = _serving_session(params, cfg)
    shapes = ses.warmup()
    manifest = ses.warmup_manifest()
    # the manifest survives the checkpoint round trip (JSON-safe)
    assert manifest == json.loads(json.dumps(manifest))
    save_pytree(tmp_path / "m", {"manifest": manifest})
    restored = load_pytree(tmp_path / "m")["manifest"]

    # a "restarted server": fresh session, same surface
    ses2 = _serving_session(params, cfg)
    assert ses2.warm_restart(restored) == shapes
    compiled = ses2._rank._cache_size()
    # live traffic on every warmed shape: zero new compiles
    for b, g in shapes:
        batch = alloc_batch(b, g, cfg.d_x, cfg.d_q)
        batch["x"][...] = np.random.default_rng(0).normal(
            size=(b, g, cfg.d_x))
        batch["mask"][...] = 1.0
        batch["m_q"][...] = float(g)
        ses2.rank_batch(batch)
    assert ses2._rank._cache_size() == compiled


def test_warm_restart_rejects_mismatched_manifest():
    masks = F.default_stage_masks(3)
    cfg = C.CascadeConfig(3, F.N_FEATURES, F.N_QUERY_BUCKETS, masks,
                          F.stage_costs(masks))
    params = C.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    ses = _serving_session(params, cfg)
    man = ses.warmup_manifest()
    wrong = dict(man, batch_groups=64)
    with pytest.raises(ValueError, match="compilation surface"):
        ses.warm_restart(wrong)
    with pytest.raises(ValueError, match="manifest version"):
        ses.warm_restart(dict(man, version=99))
