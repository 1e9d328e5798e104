"""The chunk kernel alone against the library's ragged kernel, on the chip:
one layer's attention of a prefill chunk at the benchmark cells' call
shapes (PERF.md section 6, PR 49: step 0's table). Each line of
`chiprun_out/chunk_attention_sweep.jsonl` is one call shape: us a call of
the library kernel under the blocks `kernel_blocks` had for chunks before
PR 49 (32 pages x 256 // G queries), of the chunk kernel at each
(query tile, KV block) asked for, the largest difference between the two
results, and the share of the walked pairs that a mask keeps.

    python3 benchmarks/chunk_attention_sweep.py [--tiles 256x512,128x512] [--only g7]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE = 64
# name, H, Kv, rows, table columns, keys behind the call's first row, window
SHAPES = [
    ("g7.window.deep", 28, 4, 2048, 97, 4096, 4096),
    ("g7.full.4096", 28, 4, 2048, 256, 4096, None),
    ("g7.full.8192", 28, 4, 2048, 256, 8192, None),
    ("g7.full.12288", 28, 4, 2048, 256, 12288, None),
    ("g7.full.0", 28, 4, 2048, 256, 0, None),
    ("g7.full.2048", 28, 4, 2048, 256, 2048, None),
    ("g7.window.0", 28, 4, 2048, 97, 0, 4096),
    ("g7.window.2048", 28, 4, 2048, 97, 2048, 4096),
    ("g7.window.deep.1024rows", 28, 4, 1024, 81, 4096, 4096),
    ("g7.full.6144.1024rows", 28, 4, 1024, 256, 6144, None),
    ("g8.window.deep", 32, 4, 2048, 65, 2048, 2048),
    ("g8.full.4096", 32, 4, 2048, 512, 4096, None),
    ("g8.full.12288", 32, 4, 2048, 512, 12288, None),
    ("g8.full.22528", 32, 4, 2048, 512, 22528, None),
    ("g4.full.3072", 32, 8, 2048, 128, 3072, None),
    ("g4.full.6144", 32, 8, 2048, 128, 6144, None),
    ("g16.full.4096", 32, 2, 2048, 128, 4096, None),
    ("g7.full.0.4x256rows", 28, 4, 256, 256, 0, None),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="256x256")
    ap.add_argument("--only", default="")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/chunk_attention_sweep.jsonl")
    args = ap.parse_args()
    tiles = [tuple(int(n) for n in pair.split("x")) for pair in args.tiles.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeai_tpu.ops import chunk_attention as ca
    from kubeai_tpu.ops.paged_attention import paged_attention_ragged

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a timing of the kernels needs the chip, not {device.platform}")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = fn(*a).block_until_ready()
        compile_s = time.perf_counter() - t0
        fn(*a).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(*a)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t0) / args.iters)
        return out, best * 1e6, compile_s

    for name, H, Kv, S, cols, behind, window in SHAPES:
        if args.only and not any(name.startswith(p) for p in args.only.split(",")):
            continue
        B = 4 if "4x" in name else 1
        rng = np.random.default_rng(len(name))
        pool = jnp.asarray(rng.standard_normal((B * cols + 1, PAGE, 2 * Kv, 128)), jnp.bfloat16)
        table = jnp.asarray(1 + rng.permutation(B * cols).reshape(B, cols), jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, S, H, 128)), jnp.bfloat16)
        lens = jnp.full((B,), behind + S, jnp.int32)
        G = H // Kv
        lib_blocks = (min(32, cols), min(S, 1 << (max(1, 256 // G).bit_length() - 1)))
        lib = jax.jit(lambda q, kv, tb, ln: paged_attention_ragged(q, kv, tb, ln, blocks=lib_blocks, sliding_window=window))
        want, lib_us, lib_compile = timed(lib, q, pool, table, lens)
        seen = sum(min(behind + i + 1, window or 1 << 30) for i in range(S))
        line = {
            "shape": name, "H": H, "Kv": Kv, "rows": S, "slots": B, "columns": cols, "behind": behind, "window": window,
            "library_us": round(lib_us, 1), "library_blocks": lib_blocks, "library_compile_s": round(lib_compile, 1),
            "device": device.device_kind, "chunk": [],
        }
        for tile, bk in tiles:
            if S % tile:
                continue
            mine = jax.jit(lambda q, kv, tb, ln: ca.chunk_attention_kernel(
                q, kv, tb, ln, scale=128**-0.5, sliding_window=window, tiles=(tile, bk)))
            got, us, compile_s = timed(mine, q, pool, table, lens)
            line["chunk"].append({
                "tile": tile, "kv_block": bk, "us": round(us, 1), "compile_s": round(compile_s, 1),
                "max_abs_diff": float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()),
                "hit_share": round(seen / ca.pairs_walked(S, behind, window, tile, bk, PAGE), 4),
            })
        print(json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
