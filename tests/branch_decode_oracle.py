"""The record-by-record decode of native BranchMeta records, as
``reth_tpu/trie/turbo.py`` had it until ISSUE 27: kept here, verbatim, as
the reference that the bulk ``_collect_meta_records`` is compared with."""

import numpy as np

from reth_tpu.trie.committer import BranchNode


def collect_meta_records_loop(meta_rec, key_arrays, job_starts, digests,
                              results, start_depth=0, slot_base=0):
    """Decode native BranchMeta records into per-job TrieUpdates.
    ``slot_base`` rebases the records' group-local digest slots into the
    pipeline's shared arena slot space."""
    jobs_f = meta_rec[:, 0:4].copy().view("<u4").ravel()
    reps = meta_rec[:, 4:8].copy().view("<u4").ravel()
    depths = meta_rec[:, 8:10].copy().view("<u2").ravel()
    smasks = meta_rec[:, 10:12].copy().view("<u2").ravel()
    tmasks = meta_rec[:, 12:14].copy().view("<u2").ravel()
    hmasks = meta_rec[:, 14:16].copy().view("<u2").ravel()
    cslots = meta_rec[:, 16:80].copy().view("<i4").reshape(-1, 16)
    for k in range(len(meta_rec)):
        j = int(jobs_f[k])
        keys = key_arrays[j]
        d = int(depths[k])
        key = keys[int(reps[k]) - int(job_starts[j])]  # rep_key is global
        nibs = np.empty((64,), dtype=np.uint8)
        nibs[0::2] = key >> 4
        nibs[1::2] = key & 0xF
        # BranchMeta depths are SUBTRIE-relative; the stored path must
        # skip the start_depth prefix nibbles of the full key
        path = bytes(nibs[start_depth : start_depth + d])
        hm = int(hmasks[k])
        hashes = tuple(
            digests[cslots[k, nb] + slot_base].tobytes()
            for nb in range(16) if (hm >> nb) & 1
        )
        results[j].branch_nodes[path] = BranchNode(
            int(smasks[k]), int(tmasks[k]), hm, hashes
        )
    return results
