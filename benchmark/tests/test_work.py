"""The work function counts what the reference trie needs, and the peaks table
refuses a device it does not know."""

import numpy as np
import pytest

from benchmark.harness import traffic as gen
from benchmark.harness.peaks import peaks_for
from benchmark.harness.work import (OPS_PER_PERMUTATION, keccak_work,
                                    least_seconds, trie_work)
from benchmark.reference.keccak import keccak256, keccak256_batch
from benchmark.reference.mpt import build_trie


def _naive_node_rlps(pairs: dict) -> list[bytes]:
    """Every node RLP that trie/naive.py builds for ``pairs``, captured where
    it takes a node's reference (the program's oracle, not the benchmark's)."""
    from reth_tpu.primitives.nibbles import unpack_nibbles
    from reth_tpu.trie import naive

    seen: list[bytes] = []
    real = naive.node_ref

    def spy(node_rlp: bytes) -> bytes:
        seen.append(node_rlp)
        return real(node_rlp)

    naive.node_ref = spy
    try:
        items = sorted((unpack_nibbles(k), v) for k, v in pairs.items())
        seen.append(naive._build_rlp(items, 0))      # the root node itself
    finally:
        naive.node_ref = real
    return seen


def _job(rng, n: int, clustered: bool, kind: str):
    keys = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    if clustered:
        keys[: n // 3, :3] = keys[0, :3]
    keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
    if kind == "account":
        vals = gen.account_values(rng, len(keys), {
            "contract_share": 0.2,
            "balance_len_weights": {"0": 1, "5": 1, "8": 2}})
    else:       # RLP(uint) of 1, 20 or 32 bytes: short values, as storage has
        vals = [bytes([0x80 + k]) + bytes([rng.integers(0x80, 256)])
                + rng.bytes(k - 1)
                for k in rng.choice([1, 1, 20, 32], size=len(keys)).tolist()]
    return keys, vals


@pytest.mark.parametrize("kind", ["account", "short_values"])
@pytest.mark.parametrize("n", [1, 2, 17, 400])
def test_work_equals_naive_node_rlps(kind, n):
    rng = np.random.default_rng(n)
    keys, vals = _job(rng, n, clustered=n > 2, kind=kind)
    rlps = [r for r in _naive_node_rlps(
        {k.tobytes(): v for k, v in zip(keys, vals)})]
    hashed = [r for i, r in enumerate(rlps)
              if len(r) >= 32 or i == len(rlps) - 1]
    want_hashes = len(hashed)
    want_blocks = sum(len(r) // 136 + 1 for r in hashed)
    got = trie_work([(keys, vals)], 0)
    ref = build_trie(keys, vals, 0)
    assert got == (want_hashes, want_blocks) == (ref.n_hashes, ref.n_blocks)
    work = keccak_work(*got)
    assert work["bytes"] == 136 * want_blocks + 32 * want_hashes
    assert work["ops"] == OPS_PER_PERMUTATION * want_blocks


def test_work_over_many_jobs_and_a_prefix():
    rng = np.random.default_rng(7)
    jobs = []
    for pfx in (0x1A, 0x1B, 0xF0):
        keys, vals = _job(rng, 300, clustered=False, kind="account")
        keys[:, 0] = pfx
        keys = np.unique(keys.view("S32").ravel()).view(np.uint8).reshape(-1, 32)
        jobs.append((keys, vals[: len(keys)]))
    refs = [build_trie(k, v, 2) for k, v in jobs]
    assert trie_work(jobs, 2) == (sum(r.n_hashes for r in refs),
                                  sum(r.n_blocks for r in refs))


def test_work_refuses_unsorted_keys_and_embedded_leaves():
    rng = np.random.default_rng(3)
    keys, vals = _job(rng, 10, clustered=False, kind="account")
    with pytest.raises(ValueError):
        trie_work([(keys[::-1], vals)], 0)
    deep = np.repeat(keys[:1], 2, axis=0).copy()
    deep[1, 31] ^= 1                       # two keys that differ in the last nibble
    with pytest.raises(NotImplementedError):
        trie_work([(deep, [b"\x01", b"\x02"])], 0)


def test_reference_keccak_vectors():
    assert keccak256(b"").hex() == (
        "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470")
    assert keccak256(b"abc").hex() == (
        "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45")
    msgs = [bytes([i % 251]) * n for i, n in enumerate((0, 1, 135, 136, 137, 271,
                                                       272, 600))]
    got = keccak256_batch(msgs)
    assert [g.tobytes() for g in got] == [keccak256(m) for m in msgs]


def test_ops_per_permutation_derivation():
    theta = 5 * 4 * 2 + 5 * (6 + 2) + 25 * 2
    rho = 24 * 6
    chi = 25 * 3 * 2
    assert OPS_PER_PERMUTATION == 24 * (theta + rho + chi + 2) + 17 * 2 == 10258


def test_peaks_table_raises_on_unknown_kind():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks_for("TPU v9 imaginary")


def test_least_seconds_names_its_bound():
    peaks = {"hbm_bytes_per_s": 100.0, "u32_ops_per_s": 1000.0}
    assert least_seconds({"bytes": 50.0, "ops": 100.0}, peaks) == (0.5, "bytes")
    assert least_seconds({"bytes": 5.0, "ops": 1000.0}, peaks) == (1.0, "ops")
