#!/usr/bin/env python3
"""Calibrates the one peak that has no published figure: 32-bit elementwise
logical operations per second on this device kind. Run it on the chip:

    python3 benchmark/probe_u32.py

A long chain of xor / shift / and / or / not over a u32 array resident on the
device, unrolled into ONE fused XLA program so that each element is read and
written once and the vector unit does the rest: ``rounds`` x 8 operations for
each PAIR of elements (the chain mixes two arrays). Best of several shapes and chain lengths, each timed over at
least 0.5 s of back-to-back calls ending in ``block_until_ready``. The best
rate goes into ``benchmark/harness/peaks.py`` with this script as its source;
nothing reads this script's output at run time.
"""

from __future__ import annotations

import json
import time

OPS_PER_ROUND = 8


def chain(rounds: int):
    def f(x, y):
        for r in range(rounds):
            s = 1 + (r * 7) % 31
            x = x ^ (x << s)            # 2 ops
            y = y ^ (y >> (32 - s))     # 2 ops
            x = x ^ (~y & (x | y))      # 4 ops
        return x, y

    return f


def main() -> int:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())}, "runs": []}
    for shape in ((1024, 1024), (8192, 1024), (16384, 2048)):
        for rounds in (32, 64, 128):
            fn = jax.jit(chain(rounds))
            x = jnp.arange(shape[0] * shape[1], dtype=jnp.uint32).reshape(shape)
            y = x * jnp.uint32(2654435761) + jnp.uint32(12345)
            a, b = fn(x, y)
            a.block_until_ready()
            calls, t0 = 0, time.perf_counter()
            while time.perf_counter() - t0 < 0.5 or calls < 3:
                a, b = fn(a, b)
                calls += 1
            a.block_until_ready(); b.block_until_ready()
            wall = time.perf_counter() - t0
            ops = shape[0] * shape[1] * rounds * OPS_PER_ROUND * calls
            out["runs"].append({"shape": shape, "rounds": rounds,
                                "calls": calls, "wall_s": wall,
                                "u32_ops_per_s": ops / wall,
                                "bytes_per_s": 16 * shape[0] * shape[1]
                                * calls / wall})
            print(json.dumps(out["runs"][-1]), flush=True)
    out["best_u32_ops_per_s"] = max(r["u32_ops_per_s"] for r in out["runs"])
    # a plain one-pass xor for the memory side, beside the published 819 GB/s
    x = jnp.zeros((32768, 4096), dtype=jnp.uint32)
    one = jax.jit(lambda v: v ^ jnp.uint32(0x9E3779B9))
    x = one(x); x.block_until_ready()
    calls, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < 0.5:
        x = one(x); calls += 1
    x.block_until_ready()
    out["one_pass_bytes_per_s"] = 2 * x.nbytes * calls / (time.perf_counter() - t0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
