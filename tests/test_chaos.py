"""Chaos drill engine: crash points, composed fault scenarios, invariants.

Fast tests cover the crash-point framework (spec parsing, nth counting,
real ``os._exit`` in a throwaway subprocess) and both scenario
generators' determinism. The ``@slow`` drills are the real thing:
subprocess dev nodes killed at every declared crash point (plus raw
SIGKILL) under composed ``RETH_TPU_FAULT_*`` injectors, restarted, and
held to the invariant suite — plus the Engine-API consensus domain:
seeded reorg storms (``child_consensus_victim``) verified live against
a fault-free ForkBuilder twin and then through the same restart suite.
``make test-chaos`` runs them all; tier-1 keeps its budget via
``-m 'not slow'``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from reth_tpu.chaos import (
    CRASH_POINTS,
    FAULT_MENU,
    HOTSTATE_FAULTS,
    crash_spec,
    make_consensus_scenario,
    make_scenario,
    run_scenario,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(extra=None):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RETH_TPU_FAULT_")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


# -- crash-point framework ----------------------------------------------------


def test_crash_spec_parsing(monkeypatch):
    monkeypatch.delenv("RETH_TPU_FAULT_CRASH_AT", raising=False)
    assert crash_spec() is None
    monkeypatch.setenv("RETH_TPU_FAULT_CRASH_AT", "wal-append")
    assert crash_spec() == ("wal-append", 1)
    monkeypatch.setenv("RETH_TPU_FAULT_CRASH_AT", "checkpoint-swap:4")
    assert crash_spec() == ("checkpoint-swap", 4)
    monkeypatch.setenv("RETH_TPU_FAULT_CRASH_AT", "unwind:bogus")
    assert crash_spec() == ("unwind", 1)


def test_crash_point_fires_on_nth_hit_subprocess():
    """crash_point really dies with os._exit(137) — and only on the nth
    visit. A throwaway interpreter, no node stack needed."""
    code = (
        "from reth_tpu.chaos import crash_point\n"
        "crash_point('wal-append')\n"   # hit 1: survives
        "print('alive')\n"
        "crash_point('wal-append')\n"   # hit 2: dies
        "print('unreachable')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=_env({"RETH_TPU_FAULT_CRASH_AT": "wal-append:2"}),
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r.returncode == 137
    assert "alive" in r.stdout
    assert "unreachable" not in r.stdout


def test_crash_point_ignores_other_points(monkeypatch):
    from reth_tpu import chaos

    monkeypatch.setenv("RETH_TPU_FAULT_CRASH_AT", "jar-rename")
    chaos.reset_crash_counts()
    chaos.crash_point("wal-append")  # different point: must not exit
    chaos.reset_crash_counts()


def test_declared_points_are_wired():
    """Every declared crash point has a live call site — a renamed point
    silently never firing would rot the drill matrix."""
    import reth_tpu.chaos  # noqa: F401 - CRASH_POINTS source

    wired = set()
    for rel in ("reth_tpu/storage/wal.py", "reth_tpu/storage/nippyjar.py",
                "reth_tpu/engine/tree.py"):
        src = open(os.path.join(REPO, rel)).read()
        for p in CRASH_POINTS:
            if f'crash_point("{p}")' in src:
                wired.add(p)
    assert wired == set(CRASH_POINTS)


# -- scenario generator -------------------------------------------------------


def test_make_scenario_deterministic_and_diverse():
    a, b = make_scenario(42), make_scenario(42)
    assert a == b
    scns = [make_scenario(s) for s in range(1, 40)]
    modes = {s["mode"] for s in scns}
    assert modes == {"point", "kill"}
    points = {s.get("point") for s in scns if s["mode"] == "point"}
    assert points >= set(CRASH_POINTS) - {None}
    known = set().union(*[set(f) for f in FAULT_MENU])
    for s in scns:
        assert s["faults"] and set(s["faults"]) <= known
        assert s["blocks"] >= s.get("kill_after", 0)


def test_make_consensus_scenario_deterministic_and_diverse():
    a, b = make_consensus_scenario(7), make_consensus_scenario(7)
    assert a == b
    scns = [make_consensus_scenario(s) for s in range(1, 60)]
    assert {s["mode"] for s in scns} == {"complete", "kill", "point"}
    known = set().union(*[set(f) for f in FAULT_MENU], HOTSTATE_FAULTS)
    for s in scns:
        assert s["domain"] == "consensus"
        assert s["faults"] and set(s["faults"]) <= known
        assert s["rounds"] > 0
        # hot-state injectors only land on cached seeds
        if not s.get("hot_state"):
            assert not (set(s["faults"]) & set(HOTSTATE_FAULTS))
    assert any(s.get("hot_state") for s in scns)
    assert any(set(s["faults"]) & set(HOTSTATE_FAULTS) for s in scns)
    # unwind crash points must come with a forced deep reorg (the point
    # only fires inside a persisted-chain unwind)
    for s in scns:
        if s.get("point") == "unwind":
            assert s["force_deep_reorg"]
    assert any(s["force_deep_reorg"] for s in scns)
    # storage-domain seeds stay stable: separate rng streams
    assert make_scenario(7) == make_scenario(7)


def test_fault_menu_names_real_injectors():
    """Menu entries must reference env vars the codebase actually
    parses, or a composition drills nothing."""
    import subprocess as sp

    names = sorted(set().union(*[set(f) for f in FAULT_MENU]))
    src = sp.run(["grep", "-rl", "--include=*.py", "RETH_TPU_FAULT_",
                  os.path.join(REPO, "reth_tpu")],
                 capture_output=True, text=True).stdout
    blob = "".join(open(f).read() for f in src.splitlines())
    for name in names:
        assert name in blob, f"{name} not parsed anywhere"


# -- subprocess kill drills (make test-chaos) ---------------------------------


def _drill(tmp_path, point: str, nth: int, blocks: int = 8,
           reorg_at: int = 0, timeout: int = 240):
    datadir = tmp_path / f"drill-{point}"
    datadir.mkdir()
    cmd = [sys.executable, "-m", "reth_tpu.chaos", "victim",
           "--datadir", str(datadir), "--seed", "7", "--blocks", str(blocks),
           "--threshold", "2", "--reorg-at", str(reorg_at)]
    r = subprocess.run(
        cmd, env=_env({"RETH_TPU_FAULT_CRASH_AT": f"{point}:{nth}"}),
        capture_output=True, text=True, cwd=REPO, timeout=timeout)
    assert r.returncode == 137, (
        f"{point} never fired: rc={r.returncode} {r.stderr[-400:]}")
    rec = subprocess.run(
        [sys.executable, "-m", "reth_tpu.chaos", "recover",
         "--datadir", str(datadir), "--seed", "7", "--threshold", "2"],
        env=_env(), capture_output=True, text=True, cwd=REPO, timeout=timeout)
    verdict = None
    for line in rec.stdout.splitlines():
        if line.startswith("RESULT "):
            verdict = json.loads(line[len("RESULT "):])
    assert verdict is not None, f"no verdict: {rec.stderr[-400:]}"
    assert verdict["ok"], (point, verdict["invariants"],
                           verdict.get("recovery_report"))
    return verdict


# acceptance: kill -9 at EVERY declared crash point recovers to a
# consistent head losing <= persistence_threshold blocks, with the
# recovered state root verified bit-identical by recomputation (and by
# a fault-free twin replay)
@pytest.mark.slow  # subprocess node (~8s each); `make test-chaos` runs it
@pytest.mark.parametrize("point,nth,reorg_at", [
    ("wal-append", 9, 0),
    ("checkpoint-swap", 2, 0),
    ("advance-persistence", 3, 0),
    ("unwind", 1, 5),
    ("jar-rename", 2, 0),
])
def test_kill_drill_every_crash_point(tmp_path, point, nth, reorg_at):
    verdict = _drill(tmp_path, point, nth, reorg_at=reorg_at)
    inv = verdict["invariants"]
    assert inv["root_recomputed"] and inv["twin_root"] and inv["loss_bound"]


@pytest.mark.slow
def test_kill_drill_external_sigkill(tmp_path):
    """Raw SIGKILL mid-mining (no crash point cooperation at all)."""
    scn = {"seed": 11, "faults": {}, "mode": "kill", "kill_after": 5,
           "blocks": 9, "reorg_at": 0, "threshold": 2, "hash_service": False}
    res = run_scenario(scn, tmp_path)
    assert res["ok"], (res.get("error"), res.get("invariants"))


@pytest.mark.slow  # ~1 min: the full seeded matrix; `make test-chaos` runs it
def test_chaos_campaign_ten_seeds(tmp_path):
    """Acceptance: a 10+-scenario seeded campaign of composed injectors
    x kill/restart passes the full invariant suite. Failing seeds print
    an exact replay command."""
    from reth_tpu.chaos import run_campaign

    results = run_campaign(range(1, 11), tmp_path)
    bad = [r for r in results if not r.get("ok")]
    assert not bad, [
        (r["seed"], r.get("error") or r.get("invariants")) for r in bad]


# -- Engine-API consensus domain (make test-chaos) ----------------------------


@pytest.mark.slow
def test_consensus_storm_scenario_completes(tmp_path):
    """One full reorg-storm scenario run to completion: the victim's
    live fault-free-twin invariants hold under the composed injectors,
    and the restart invariant suite passes afterwards."""
    scn = make_consensus_scenario(1)
    assert scn["mode"] == "complete"  # pin: seed 1 runs the whole storm
    res = run_scenario(scn, tmp_path)
    assert res["ok"], (res.get("error"), res.get("invariants"))


@pytest.mark.slow  # ~2 min: the full seeded matrix; `make test-chaos` runs it
def test_consensus_campaign_ten_seeds(tmp_path):
    """Acceptance: a 10-seed Engine-API adversarial campaign — reorg
    storms (side forks, deep reorgs across the persistence threshold,
    orphans, duplicates, invalid floods, hostile fcU targets) composed
    with the PR 1-11 injectors and crash points/SIGKILLs — passes the
    full invariant suite: canonical chain + roots bit-identical to the
    fault-free twin, no leaked lease/lock, health back to ok within the
    SLO window, node mines again. Failing seeds print a replay command."""
    from reth_tpu.chaos import run_campaign

    results = run_campaign(range(1, 11), tmp_path, domain="consensus")
    bad = [r for r in results if not r.get("ok")]
    assert not bad, [
        (r["seed"], r.get("error") or r.get("invariants")) for r in bad]


@pytest.mark.slow
def test_deep_reorg_across_threshold_sigkill_restart(tmp_path):
    """Satellite acceptance: a deep reorg across the persistence
    threshold followed by SIGKILL + restart — recovered head, re-served
    branch point, and root verification all consistent."""
    scn = {"domain": "consensus", "seed": 33, "faults": {}, "mode": "kill",
           "kill_after": 8, "rounds": 0, "threshold": 2,
           "hash_service": False, "force_deep_reorg": True}
    res = run_scenario(scn, tmp_path)
    assert res["ok"], (res.get("error"), res.get("invariants"))
    inv = res["invariants"]
    assert inv["root_recomputed"] and inv["twin_root"] and inv["loss_bound"]
    # the storm really reorged below the persistence threshold before the
    # kill (marker written ahead of the unwinding fcU), and the recovered
    # chain re-serves the branch point: the head sits at-or-above every
    # reorg target with its ancestry twin-verified
    rec = (tmp_path / "scn-33" / "chaos_blocks.jsonl").read_text()
    markers = [json.loads(l)["reorg_to"] for l in rec.splitlines()
               if "reorg_to" in l]
    assert markers, "no deep-reorg intent recorded before the kill"
    assert res["recovered"]["number"] >= min(markers)


@pytest.mark.slow
def test_torn_record_accepted_is_caught_end_to_end(tmp_path):
    """Acceptance: a deliberately broken recovery (torn WAL record
    accepted via RETH_TPU_FAULT_WAL_ACCEPT_TORN) is caught by the
    invariant suite — proving the harness can fail."""
    datadir = tmp_path / "torn"
    datadir.mkdir()
    r = subprocess.run(
        [sys.executable, "-m", "reth_tpu.chaos", "victim",
         "--datadir", str(datadir), "--seed", "3", "--blocks", "6",
         "--threshold", "2"],
        env=_env(), capture_output=True, text=True, cwd=REPO, timeout=240)
    assert r.returncode == 0, r.stderr[-400:]
    from reth_tpu.chaos import inject_bad_crc_record
    from reth_tpu.primitives.keccak import keccak256_batch_np
    from reth_tpu.primitives.secp256k1 import address_from_priv
    from reth_tpu.storage.tables import Tables

    victim_addr = address_from_priv(0xA11CE + 3)
    hkey = keccak256_batch_np([victim_addr])[0]

    def inject():
        # bit-rot a hashed account through a bad-CRC record appended to
        # the live segment (each graceful stop truncates the log, so the
        # record must be re-injected after every recover run)
        inject_bad_crc_record(datadir / "wal", {
            Tables.HashedAccounts.name: {
                "rows": {hkey: b"\xde\xad" * 24}, "del": []}})

    def recover(extra_env):
        rec = subprocess.run(
            [sys.executable, "-m", "reth_tpu.chaos", "recover",
             "--datadir", str(datadir), "--seed", "3", "--threshold", "2"],
            env=_env(extra_env), capture_output=True, text=True, cwd=REPO,
            timeout=240)
        for line in rec.stdout.splitlines():
            if line.startswith("RESULT "):
                return json.loads(line[len("RESULT "):])
        raise AssertionError(f"no verdict: {rec.stderr[-400:]}")

    # correct reader: tail discarded, everything passes
    inject()
    good = recover({})
    assert good["ok"], good["invariants"]
    # broken reader: the corruption lands — the suite must catch it
    inject()
    bad = recover({"RETH_TPU_FAULT_WAL_ACCEPT_TORN": "1"})
    assert not bad["ok"]
    assert not (bad["invariants"]["root_recomputed"]
                and bad["invariants"]["head_consistent"])
