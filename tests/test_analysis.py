"""Self-tests for cascade-lint (repro.analysis).

Every rule is tested in both directions: it MUST flag its seeded
violation in the fixture corpus, and MUST NOT flag the live tree.  The
cross-file rules (CL007 seams, CL011 identity) are additionally tested
against doctored copies of the real serving sources, so deleting the
invariant — not just violating it — is caught.  The runtime lock-order
witness gets its own inversion scenario: a deliberate two-thread
opposite-order acquisition that never actually deadlocks, caught purely
from the recorded order graph (and the same pattern caught statically
from the bad_lock_cycle fixture).
"""
import ast
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from repro.analysis import accounting, containment, core, locks
from repro.analysis.witness import (
    LockOrderInversion,
    LockOrderWitness,
    _WitnessedLock,
    install_witness,
)

REPO = core.REPO_ROOT
FIX = core.FIXTURES_DIR


@pytest.fixture(scope="module")
def live_findings():
    files = core.collect_files(core.default_targets())
    return core.run(files)


def _pf(rel: str, src: str) -> core.ParsedFile:
    return core.ParsedFile(Path(rel), rel, ast.parse(src), src)


def _fixture_rules(name: str) -> set:
    files = core.collect_files([FIX / name])
    assert len(files) == 1
    return {f.rule for f in core.run(files)}


def test_live_tree_clean(live_findings):
    assert not live_findings, "\n".join(str(f) for f in live_findings)


def test_registry_covers_all_rules():
    assert set(core.all_rules()) == {f"CL{i:03d}" for i in range(1, 12)}


@pytest.mark.parametrize("fixture,rule", [
    ("bad_lock_block.py", "CL001"),
    ("bad_lock_cycle.py", "CL002"),
    ("bad_jit.py", "CL003"),
    ("bad_shape.py", "CL004"),
    ("bad_clock.py", "CL005"),
    ("bad_rng.py", "CL006"),
    ("bad_except.py", "CL007"),
    ("bad_future.py", "CL008"),
    ("bad_stats.py", "CL009"),
    ("bad_stats.py", "CL010"),
    ("bad_identity_serve.py", "CL011"),
])
def test_fixture_flags_seeded_violation(fixture, rule, live_findings):
    assert rule in _fixture_rules(fixture)
    # ...and the same rule is silent on the live tree
    assert rule not in {f.rule for f in live_findings}


def test_cl004_fires_on_both_hand_rolled_staging_forms():
    """The one-buffer layout built by hand, and the four-array form that
    would be a second path to the device, each outside the bucket/warmup
    code."""
    files = core.collect_files([FIX / "bad_shape.py"])
    hits = [f for f in core.run(files) if f.rule == "CL004"]
    assert sorted(f.why.split("`")[1] for f in hits) == [
        "four_array_batch", "handmade_batch"]


def test_default_walk_skips_fixture_corpus():
    files = core.collect_files(core.default_targets())
    assert not any("analysis/fixtures" in f.rel for f in files)
    # but explicit paths always get in
    files = core.collect_files([FIX / "bad_clock.py"])
    assert len(files) == 1


# ---- doctored-source direction for the cross-file rules ----------------

def test_cl011_fires_when_identity_deleted():
    rel = "src/repro/launch/serve.py"
    real = (REPO / rel).read_text()
    assert not [f for f in accounting.check([_pf(rel, real)])
                if f.rule == "CL011"]
    doctored = real.replace(
        'st["submitted"] != st["completed"] + st["shed"] + st["errors"]',
        "False")
    assert doctored != real
    assert any(f.rule == "CL011"
               for f in accounting.check([_pf(rel, doctored)]))


def test_cl007_fires_when_seam_loses_noqa():
    rel = "src/repro/serving/pump.py"
    real = (REPO / rel).read_text()
    assert not [f for f in containment.check([_pf(rel, real)])
                if f.rule == "CL007"]
    doctored = real.replace("# noqa: BLE001", "#", 1)
    assert doctored != real
    found = [f for f in containment.check([_pf(rel, doctored)])
             if f.rule == "CL007"]
    assert found and "noqa" in found[0].why


def test_cl001_fires_on_seeded_block_in_real_session():
    rel = "src/repro/serving/session.py"
    real = (REPO / rel).read_text()
    doctored = real.replace('self.stats["submitted"] += 1',
                            'self.stats["submitted"] += 1; '
                            'self._sleep(0.01)', 1)
    assert doctored != real
    assert any(f.rule == "CL001" for f in locks.check([_pf(rel, doctored)]))


# ---- CLI ----------------------------------------------------------------

def _run_cli(args, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    return subprocess.run([sys.executable, "-m", "repro.analysis", *args],
                          cwd=cwd, env=env, capture_output=True, text=True)


def test_cli_clean_tree_exit_zero_and_report(tmp_path):
    report = tmp_path / "ANALYSIS_report.json"
    proc = _run_cli(["--report", str(report)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(report.read_text())
    assert data["ok"] is True
    assert data["files_scanned"] > 50
    assert len(data["rules"]) == 11


def test_cli_nonzero_on_fixture(tmp_path):
    report = tmp_path / "r.json"
    proc = _run_cli(["--report", str(report),
                     str(FIX / "bad_clock.py")])
    assert proc.returncode == 1
    data = json.loads(report.read_text())
    assert data["ok"] is False
    f = data["findings"][0]
    assert set(f) == {"rule", "file", "line", "why"}
    assert f["rule"] == "CL005" and f["line"] == 6


# ---- runtime lock-order witness ----------------------------------------

def test_witness_catches_two_thread_inversion():
    w = LockOrderWitness()
    a = w.wrap(threading.Lock(), "a")
    b = w.wrap(threading.Lock(), "b")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    # run to completion sequentially — no deadlock ever happens, the
    # inversion is caught purely from the recorded order graph
    for fn in (ab, ba):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    assert w.inversions
    with pytest.raises(LockOrderInversion):
        w.assert_clean()


def test_witness_consistent_order_is_clean():
    w = LockOrderWitness()
    a = w.wrap(threading.Lock(), "a")
    b = w.wrap(threading.Lock(), "b")
    for _ in range(3):
        with a:
            with b:
                pass
    w.assert_clean()


def test_witness_rlock_reentry_is_not_an_edge():
    w = LockOrderWitness()
    r = w.wrap(threading.RLock(), "session")
    with r:
        with r:
            pass
    assert not w.edges
    w.assert_clean()


def test_witness_distinct_instances_are_distinct_nodes():
    # two replicas' session locks taken in "opposite" order are NOT an
    # inversion — identity is id()-level, not name-level
    w = LockOrderWitness()
    s1 = w.wrap(threading.Lock(), "session@1")
    s2 = w.wrap(threading.Lock(), "session@2")
    with s1:
        with s2:
            pass
    w.assert_clean()


def test_install_witness_wraps_and_uninstalls():
    from repro.serving.batching import TransferBufferPool
    witness, uninstall = install_witness()
    try:
        pool = TransferBufferPool(4, 3)
        assert isinstance(pool._lock, _WitnessedLock)
        buf = pool.acquire(2, 4)  # exercise the wrapped lock
        pool.release(buf)
        witness.assert_clean()
    finally:
        uninstall()
    assert not isinstance(TransferBufferPool(4, 3)._lock, _WitnessedLock)


def test_static_graph_catches_the_same_inversion_pattern():
    # the static twin of the runtime scenario above (satellite): the
    # bad_lock_cycle fixture encodes the session/router opposite-order
    # pattern and CL002 must find the cycle
    files = core.collect_files([FIX / "bad_lock_cycle.py"])
    found = [f for f in locks.check(files) if f.rule == "CL002"]
    assert found and "session" in found[0].why and "router" in found[0].why


# ---------------------------------------------------------------------------
# Layer direction: kernels <- core.cascade <- {core.pipeline, core.losses}
# <- {core.trainer, serving}. Read with ast only, like the rules above; an
# import inside a function counts as much as one at the top of a module.
# ---------------------------------------------------------------------------

SRC = REPO / "src"


def _imported_modules(path: Path) -> set[str]:
    """Every module an `import` or `from ... import` in the file names,
    at any depth: `from a.b import c` yields both a.b and a.b.c."""
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


# each layer, and the modules it must not import
LAYER_RULES = {
    "repro.kernels": ("repro.core", "repro.serving"),
    "repro.core.cascade": ("repro.core.pipeline", "repro.core.losses",
                           "repro.serving"),
    "repro.core.losses": ("repro.core.pipeline", "repro.serving"),
    "repro.core.pipeline": ("repro.serving",),
}


@pytest.mark.parametrize("layer", LAYER_RULES)
def test_layers_import_one_way(layer):
    forbidden = LAYER_RULES[layer]
    base = SRC.joinpath(*layer.split("."))
    files = (sorted(base.rglob("*.py")) if base.is_dir()
             else [base.with_suffix(".py")])
    assert files
    bad = sorted(f"{f.relative_to(SRC)} imports {name}"
                 for f in files for name in _imported_modules(f)
                 if any(name == m or name.startswith(m + ".")
                        for m in forbidden))
    assert not bad, "\n".join(bad)
