#!/usr/bin/env python3
"""The dropless expert layer alone (``ops.moe``), at the shapes the benchmark's
three expert configurations give it, on the chip: milliseconds a call of the
three grouped products over sorted rows, and of ``moe_infer`` whole (router,
sort, gather, products, combine), by layout and weight tile.

    chiprun -- python3 scripts/grouped_matmul_bench.py                     # this tree
    chiprun -- python3 scripts/grouped_matmul_bench.py --root .bench_checkout/parent --tag parent
    ... --tile-mib 3,6                 # a fixed weight tile budget in the constant's place
    ... --shapes laguna-s-ep2.chunk-512,laguna-s-ep2.decode

One process a tree (``--root`` is imported in place of this checkout, so a
parent unpacked with ``git archive`` is timed by the same code on the same
chip). A tree that has ``tile_aligned_groups`` is timed three times a shape:
``dense`` (a group starts where the one before ended), ``aligned`` (every group
starts on a row tile) and ``rule`` (what the shapes choose); a tree without it
once, as ``parent``. A line is one (shape, layout, weight tile): ``--reps``
calls chained inside one program (a call's output feeds the next one's input,
so the host's dispatch is paid once), the median of ``--calls`` such programs
after one that warms it up, over the reps. Routing is even (random inputs and a
random router), so an expert gets about ``tokens x k / router width`` rows. The
products' chain adds its output to its rows over the whole row budget, which a
tile-aligned buffer makes longer: ``layer_ms`` is the fair reading of a layout,
``products_ms`` of a tile. The lines
land on stdout and in ``chiprun_out/grouped_matmul_bench/<tag>.jsonl``. A time
is a chip's: on another backend the script stops (``--allow-cpu`` walks the code
path at a sixteenth of the widths and keeps no time).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]

# configuration: (d_model, expert width, held experts, router width, zero-compute
# experts, top-k); benchmark/configs/*.json
CONFIGS = {
    "laguna-s-ep2": (3072, 1024, 128, 256, 0, 10),
    "qwen3-next-ep2": (2048, 512, 256, 512, 0, 10),
    "longcat-flash-ep32": (6144, 2048, 16, 768, 256, 12),
}
# name: (configuration, tokens a group): a prompt's chunk at 5-40 rows an expert
# under even routing, and the decode step (every slot live)
SHAPES = {
    **{f"laguna-s-ep2.chunk-{t}": ("laguna-s-ep2", t) for t in (128, 256, 512, 1024)},
    "laguna-s-ep2.decode": ("laguna-s-ep2", 32),
    **{f"qwen3-next-ep2.chunk-{t}": ("qwen3-next-ep2", t) for t in (256, 512, 1024, 2048)},
    "qwen3-next-ep2.decode": ("qwen3-next-ep2", 128),
    **{f"longcat-flash-ep32.chunk-{t}": ("longcat-flash-ep32", t) for t in (512, 1024)},
    "longcat-flash-ep32.decode": ("longcat-flash-ep32", 64),
}
LAYERS = 2  # the expert stacks go to the kernel whole, with the layer


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=str(HERE), help="the checkout whose op is timed")
    parser.add_argument("--tag", default=None, help="name of the output file (default: the root's)")
    parser.add_argument("--tile-mib", default="",
                        help="weight tiles (MiB, one buffer) to sweep in the constant's place")
    parser.add_argument("--shapes", default="", help="names to run, comma-separated (default: all)")
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--reps", type=int, default=8)
    parser.add_argument("--allow-cpu", action="store_true",
                        help="code-path check only: no times kept")
    args = parser.parse_args()
    root = Path(args.root).resolve()
    sys.path.insert(0, str(root))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from prime_tpu.ops import moe

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.allow_cpu:
        raise SystemExit(f"a time is a chip's: this backend is {platform!r}")
    shrink = 1 if platform == "tpu" else 16
    tile = moe.MOE_ROW_TILE
    rule = getattr(moe, "tile_aligned_groups", None)  # the parent lays every group dense
    layouts = {"parent": None}
    if rule:
        layouts = {"dense": lambda *_: False, "aligned": lambda *_: True, "rule": rule}
    budgets = [float(b) for b in args.tile_mib.split(",") if b] if rule else []
    names = [n for n in args.shapes.split(",") if n] or list(SHAPES)
    out = HERE / "chiprun_out" / "grouped_matmul_bench" / f"{args.tag or root.name}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    lines = []

    def timed(chain, *operands):
        """(milliseconds a rep, the chain's output)."""
        result = jax.block_until_ready(chain(*operands))
        times = []
        for _ in range(args.calls):
            t0 = time.perf_counter()
            jax.block_until_ready(chain(*operands))
            times.append((time.perf_counter() - t0) * 1e3 / args.reps)
        return (statistics.median(times) if platform == "tpu" else None), result

    weights = {}
    for name in names:
        config, tokens = SHAPES[name]
        d_model, d_ff, held, width, n_zero, k = CONFIGS[config]
        d_model, d_ff = d_model // shrink, d_ff // shrink
        if config not in weights:
            weights.clear()  # one configuration's stacks on the device at a time
            keys = jax.random.split(jax.random.PRNGKey(0), 4)
            normal = lambda i, fan_in, *shape: (  # noqa: E731
                jax.random.normal(keys[i], shape, jnp.float32 if i == 0 else jnp.bfloat16)
                * fan_in**-0.5
            )
            weights[config] = (
                normal(0, d_model, d_model, width),
                normal(1, d_model, LAYERS, held, d_model, d_ff),
                normal(2, d_model, LAYERS, held, d_model, d_ff),
                normal(3, d_ff, LAYERS, held, d_ff, d_model),
            )
        router_w, w_gate, w_up, w_down = weights[config]
        layer = jnp.int32(LAYERS - 1)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, d_model), jnp.bfloat16)
        # the same even routing for the products alone: every token's k picks without
        # replacement over the router's width, the held ones counted by expert
        rng = np.random.default_rng(0)
        picks = np.argsort(rng.random((tokens, width)), axis=1)[:, :k]
        sizes = np.bincount(picks[picks < held], minlength=held).astype(np.int32)
        for layout, choose in layouts.items():
            aligned = bool(choose and choose(tokens, k, width))
            if rule:
                moe.tile_aligned_groups = choose
            for budget in budgets or [None]:
                if budget is not None:
                    if layout == "rule":
                        continue
                    moe.GMM_WEIGHT_VMEM_BYTES = int(2 * budget * 2**20)
                spans = -(-sizes // tile) * tile if aligned else sizes
                rows_m = -(-tokens * min(k, held) // tile) * tile  # the parent's, dense
                if rule:
                    rows_m = moe.row_budget(tokens, k, held, width)
                rows = jax.random.normal(jax.random.PRNGKey(2), (rows_m, d_model), jnp.bfloat16)

                @jax.jit
                def products(rows, w_gate, w_up, w_down, spans, layer):
                    def call(_, rows):
                        out = moe._expert_rows(
                            rows, jnp.zeros((rows_m,), jnp.int32), w_gate, w_up, w_down,
                            spans, layer, None, None, None, 0.0, 1.702,
                        )
                        return (rows + out).astype(rows.dtype)

                    return jax.lax.fori_loop(0, args.reps, call, rows)

                @jax.jit
                def whole(x, router_w, w_gate, w_up, w_down, layer):
                    def call(_, carry):
                        x, _ = carry
                        y, stats = moe.moe_infer(
                            x, router_w, w_gate, w_up, w_down, k, layer=layer, n_zero=n_zero,
                        )
                        return (x + y).astype(x.dtype), stats

                    zero = jnp.zeros((len(moe.MOE_STATS),), jnp.int32)
                    return jax.lax.fori_loop(0, args.reps, call, (x, zero))

                products_ms, _ = timed(
                    products, rows, w_gate, w_up, w_down, jnp.asarray(spans), layer
                )
                layer_ms, (_, stats) = timed(whole, x, router_w, w_gate, w_up, w_down, layer)
                counts = dict(zip(moe.MOE_STATS, np.asarray(stats).tolist()))  # the last call's
                tiling = getattr(moe, "grouped_matmul_tiling", None)
                line = {
                    "tree": args.tag or root.name, "shape": name, "layout": layout,
                    "aligned": aligned, "rows_m": rows_m,
                    "tiles": tiling and [list(tiling(*w.shape[2:], 2)) for w in (w_gate, w_down)],
                    "rows_per_touched": round(counts["local"] / max(1, counts["experts_touched"]), 2),
                    "touched": counts["experts_touched"],
                    "visits": counts["rows_computed"] // tile,
                    "weight_reads": counts.get("weight_reads"),
                    "products_ms": products_ms, "layer_ms": layer_ms,
                    "device": jax.devices()[0].device_kind,
                }
                lines.append(line)
                print(json.dumps(line), flush=True)
    out.write_text("".join(json.dumps(line) + "\n" for line in lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
