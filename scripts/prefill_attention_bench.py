#!/usr/bin/env python3
"""``ops.attention.cache_prefill_attention`` alone, at the shapes the benchmark's
configurations give it, on the chip: milliseconds a call by chunk offset.

    chiprun -- python3 scripts/prefill_attention_bench.py                  # this tree
    chiprun -- python3 scripts/prefill_attention_bench.py --root .bench_checkout/parent --tag parent
    ... --blocks 256,512,1024          # a fixed block in the rule's place, where the tree walks blocks

One process a tree (``--root`` is imported in place of this checkout, so a
parent unpacked with ``git archive`` is timed by the same code on the same
chip). A line is one (shape, offset, block): ``--reps`` calls chained inside one
program (a call's output feeds the next one's queries, so the host's dispatch is
paid once), the median of ``--calls`` such programs after one that warms it up,
over the reps. The lines land on stdout and in
``chiprun_out/prefill_attention_bench/<tag>.jsonl``. A time is a chip's: on
another backend the script stops (``--allow-cpu`` walks the code path at one row
a wave and keeps no time).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]

# name: (queries (B, H, S, D), stacked K/V leaf (L, B, KH, D, C), K and V one leaf).
# The first four are a configuration's widest chunk prefill of a full layer over
# its longest row (benchmark/configs/*.json); the rest are the rows and waves its
# traffic mostly gives it.
SHAPES = {
    "laguna-s-ep2": ((1, 48, 512, 128), (2, 1, 8, 128, 8192), False),
    "qwen3-4b": ((8, 32, 512, 128), (2, 8, 8, 128, 2048), False),
    "qwen3-next-ep2": ((1, 16, 2048, 256), (2, 1, 2, 256, 4096), False),
    "longcat-flash-ep32": ((4, 64, 512, 576), (2, 4, 1, 576, 2048), True),
    "laguna-s-ep2.row-3072": ((1, 48, 512, 128), (2, 1, 8, 128, 3072), False),
    "qwen3-4b.wave-1": ((1, 32, 512, 128), (2, 1, 8, 128, 2048), False),
    "qwen3-4b.wave-1.row-512": ((1, 32, 512, 128), (2, 1, 8, 128, 512), False),
    "qwen3-4b.wave-2.row-512": ((2, 32, 512, 128), (2, 2, 8, 128, 512), False),
    "qwen3-4b.wave-8.row-512": ((8, 32, 512, 128), (2, 8, 8, 128, 512), False),
    "qwen3-4b.wave-1.chunk-256": ((1, 32, 256, 128), (2, 1, 8, 128, 256), False),
    "longcat-flash-ep32.wave-1.row-128": ((1, 64, 128, 576), (2, 1, 1, 576, 128), True),
    "longcat-flash-ep32.wave-2.row-256": ((2, 64, 256, 576), (2, 2, 1, 576, 256), True),
    "longcat-flash-ep32.wave-4.row-512": ((4, 64, 512, 576), (2, 4, 1, 576, 512), True),
    "phi4-mini-flash.wave-2": ((2, 40, 512, 128), (1, 2, 10, 128, 4096), False),
    "phi4-mini-flash.wave-1.row-512": ((1, 40, 512, 128), (1, 1, 10, 128, 512), False),
    "olmo-hybrid-7b-pp2": ((1, 30, 512, 128), (2, 1, 30, 128, 2048), False),
    "qwen3-next-ep2.row-2048": ((1, 16, 2048, 256), (2, 1, 2, 256, 2048), False),
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=str(HERE), help="the checkout whose op is timed")
    parser.add_argument("--tag", default=None, help="name of the output file (default: the root's)")
    parser.add_argument("--blocks", default="", help="fixed block widths to sweep in the rule's place")
    parser.add_argument("--shapes", default="", help="names to run, comma-separated (default: all)")
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--reps", type=int, default=8)
    parser.add_argument("--allow-cpu", action="store_true", help="code-path check only: no times kept")
    args = parser.parse_args()
    root = Path(args.root).resolve()
    sys.path.insert(0, str(root))

    import jax
    import jax.numpy as jnp

    from prime_tpu.ops import attention

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.allow_cpu:
        raise SystemExit(f"a time is a chip's: this backend is {platform!r}")
    rule = getattr(attention, "prefill_attention_block", None)  # the parent scores the whole row
    sweep = [int(b) for b in args.blocks.split(",") if b] if rule else []
    names = [n for n in args.shapes.split(",") if n] or list(SHAPES)
    out = HERE / "chiprun_out" / "prefill_attention_bench" / f"{args.tag or root.name}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    lines = []
    for name in names:
        q_shape, kv_shape, shared = SHAPES[name]
        if platform != "tpu":
            q_shape, kv_shape = (1, *q_shape[1:]), (*kv_shape[:1], 1, *kv_shape[2:])
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(keys[0], q_shape, jnp.bfloat16)
        k = jax.random.normal(keys[1], kv_shape, jnp.bfloat16)
        v = k if shared else jax.random.normal(keys[2], kv_shape, jnp.bfloat16)
        seq, capacity = q_shape[2], kv_shape[-1]
        offsets = sorted({0, (capacity // 2 // seq) * seq, capacity - seq})
        for width in sweep or [None]:
            if width is not None:
                if capacity % width:
                    continue
                attention.prefill_attention_block = lambda capacity, width=width: min(capacity, width)
            block = attention.prefill_attention_block(capacity) if rule else capacity

            @jax.jit
            def chain(q, k, v, offset, layer):
                def call(_, q):
                    out = attention.cache_prefill_attention(
                        q, k, k if shared else v, offset, q_shape[-1] ** -0.5, layer=layer
                    )
                    return (q + out).astype(q.dtype)

                return jax.lax.fori_loop(0, args.reps, call, q)

            for offset in offsets:
                operands = (q, k, v, jnp.int32(offset), jnp.int32(kv_shape[0] - 1))
                chain(*operands).block_until_ready()
                times = []
                for _ in range(args.calls):
                    t0 = time.perf_counter()
                    chain(*operands).block_until_ready()
                    times.append((time.perf_counter() - t0) * 1e3 / args.reps)
                line = {"tree": args.tag or root.name, "shape": name, "offset": offset, "block": block,
                        "ms": statistics.median(times) if platform == "tpu" else None,
                        "device": jax.devices()[0].device_kind}
                lines.append(line)
                print(json.dumps(line), flush=True)
        if rule:
            attention.prefill_attention_block = rule
    out.write_text("".join(json.dumps(line) + "\n" for line in lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
