"""Driver ``rebuild_storage_big``: the ``rebuild_storage`` driver over the
storage chunk that a trie larger than ``chunk_leaves`` closes: thousands of
small whole tries and then ONE of millions of slots, through the same
``commit_hashed_pipelined(jobs, collect_branches=True, start_depth=0)`` on the
one committer kept for the run. Only where the operations come from is its
own (``harness/traffic_storage_big.py``); the window, the answers kept,
``release``, the work count (the reference's: a trie of millions of uniform
keys with one-byte values always holds an embedded leaf, so ``trie_work``
raises), the comparison with ``reference/mpt_many.py::build_tries`` and its
limits (all 0) are the parent class's.
"""

from __future__ import annotations

from benchmark.drivers import rebuild_storage
from benchmark.harness import traffic_storage_big as gen


class Driver(rebuild_storage.Driver):
    def setup(self) -> None:
        self.ops = gen.big_chunk_ops(self.traffic, self.seed)
        self.committer = self.make_committer()
        self.start_depth = int(self.call["start_depth"])
        # warm-up, untimed: every distinct chunk once
        for op in range(len(self.ops)):
            self._commit(op)
