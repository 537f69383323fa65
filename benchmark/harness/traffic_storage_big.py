"""The generator of the storage chunk that a trie larger than the chunk closes
(``"kind": "storage_chunk_closed_by_big_trie"``). ``MerkleStage._storage_chunk``
adds WHOLE tries ``while leaves < chunk_leaves``, so a trie of millions of slots
is never a chunk of its own: it is the LAST job of a chunk that already holds
between 0 and ``chunk_leaves`` slots of small tries in hashed-address order.
One operation is that chunk: the fill, then the big trie, each a job
``(keys (n, 32) uint8 ascending, values list[bytes])``. The law, the order and
the keys and values are ``harness/traffic_storage.py``'s, imported, not copied.
Nothing here imports the program.

The sizes come from the PARAMETERS, never from ``--seed``:

- the fill (``fill_before``, rule ``half_chunk``): the loop's fill when the
  big trie arrives is uniform on 0 .. ``chunk_leaves``, so half a chunk stands
  for it: ``power_law_sizes(law, chunk_leaves // 2)``, the law's quantiles as
  in ``rebuild.storage``, in ``_order``'s permutation keyed by (T, operation);
- the big trie (``big_trie``): by rule
  ``slot_weighted_median_over_chunk_leaves`` the size s at which half of the
  slots that the law puts in tries larger than ``chunk_leaves`` lie in tries of
  s or more; or ``{"slots": n}``, a stated size, for rehearsals and tests
  whose small law has no trie over its chunk.

So groups, windows, tiers and the work count are the same on every seed. From
``--seed``: every key and every value, as ``traffic_storage._jobs`` draws them.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.traffic import make_rng
from benchmark.harness.traffic_storage import (_cdf, _jobs, _order,
                                               power_law_sizes)

KIND = "storage_chunk_closed_by_big_trie"


def slot_quantile_over(law: dict, chunk_leaves: int, q: float = 0.5) -> int:
    """Of the slots the law puts in tries LARGER than ``chunk_leaves``, the
    share ``q`` lies in tries smaller than the size returned (q = 1/2: the
    slot-weighted median of those tries)."""
    cdf = _cdf(law)
    if len(cdf) <= chunk_leaves:
        raise ValueError(f"the law has no trie over {chunk_leaves} slots: "
                         f"state the big trie's size")
    # diff(cdf)[k] = P(s = k + 2): from s = chunk_leaves + 1 on
    sizes = np.arange(chunk_leaves + 1, len(cdf) + 1, dtype=np.float64)
    slots = np.cumsum(np.diff(cdf)[chunk_leaves - 1:] * sizes)
    return int(chunk_leaves + 1 + np.searchsorted(slots, q * slots[-1]))


def big_trie_slots(shape: dict) -> int:
    big = shape["big_trie"]
    if "slots" in big:
        return int(big["slots"])
    if big["rule"] != "slot_weighted_median_over_chunk_leaves":
        raise ValueError(f"unknown big-trie rule {big['rule']!r}")
    return slot_quantile_over(shape["size_law"], int(shape["chunk_leaves"]))


def fill_sizes(shape: dict) -> np.ndarray:
    """The small tries that are in the chunk when the big trie arrives,
    ascending."""
    if shape["fill_before"]["rule"] != "half_chunk":
        raise ValueError(f"unknown fill rule {shape['fill_before']['rule']!r}")
    return power_law_sizes(shape["size_law"], int(shape["chunk_leaves"]) // 2)


def _in_order(fill: np.ndarray, big: int, op: int) -> np.ndarray:
    """The fill in hashed-address order, then the trie that closes the chunk."""
    return np.append(fill[_order(len(fill), op)], big)


def chunk_sizes(traffic: dict, op: int) -> np.ndarray:
    """Operation ``op``'s trie sizes in the order the stage takes them."""
    shape = traffic["jobs"]
    return _in_order(fill_sizes(shape), big_trie_slots(shape), op)


def big_chunk_ops(traffic: dict, seed: int) -> list[list]:
    """``distinct_ops`` chunks, sizes and order from the parameters, keys and
    values from the seed."""
    if traffic["kind"] != KIND:
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    rng = make_rng(seed, 3)
    shape, weights = traffic["jobs"], traffic["values"]["rlp_len_weights"]
    fill, big = fill_sizes(shape), big_trie_slots(shape)
    return [_jobs(rng, _in_order(fill, big, o), weights)
            for o in range(int(traffic["distinct_ops"]))]
