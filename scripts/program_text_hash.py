#!/usr/bin/env python3
"""Which benchmark configurations' device programs did a change alter?

sha256 of the lowered (StableHLO) text of each benchmark configuration's
engine-shaped decode step and widest chunk prefill, from abstract shapes at the
served size (``benchmark/configs/*.json``: preset, slots, slot capacity,
``max_wave``), lowered for a described v5e with the gates a TPU backend opens
(the flash-decode kernel, megablox). Nothing is compiled or run: six
configurations take some 15 s on the CPU. The same hash on two trees is the same
device program, kernel bodies aside: a Pallas call's body is serialized with the
checkout's path in it and is left out of the hash (``tests/test_kernels.py``
holds the kernels themselves).

    python scripts/program_text_hash.py            # print, and compare with the record
    python scripts/program_text_hash.py --write    # record this tree's hashes

The record is ``tests/program_text_hashes.json``; ``tests/test_program_texts.py``
holds every configuration to it, so a PR that moves another configuration's
program says so by re-recording it.
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
RECORD = ROOT / "tests" / "program_text_hashes.json"
_KERNEL_BODY = re.compile(r'\\22body\\22: \\22[^\\]*\\22')


def served() -> dict[str, dict]:
    """Configuration name -> its preset and ``serve`` block, from the benchmark's files."""
    out = {}
    for path in sorted((ROOT / "benchmark" / "configs").glob("*.json")):
        config = json.loads(path.read_text())
        out[path.stem] = {"preset": config.get("preset", path.stem), **config["serve"]}
    return out


@contextlib.contextmanager
def chip_gates():
    """What a TPU backend chooses, on the CPU (tests/test_aot_lowering.py's
    ``_chip_takes_the_kernels``): the flash-decode kernel for a long row, megablox
    for the grouped products."""
    from prime_tpu.ops import attention, moe

    was = attention._decode_pallas_eligible, moe._kernel_path
    attention._decode_pallas_eligible = lambda k: k.shape[-1] >= 2048
    moe._kernel_path = lambda: (True, False)
    try:
        yield
    finally:
        attention._decode_pallas_eligible, moe._kernel_path = was


def hashes(name: str, serve: dict) -> dict:
    """``prefill_shapes`` and the two programs' hashes for one configuration.
    Call under ``chip_gates()``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from prime_tpu.models.config import get_config
    from prime_tpu.models.llama import forward, init_cache, init_params
    from prime_tpu.serve.engine import prefill_shapes

    one = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    )

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    config = get_config(serve["preset"])
    slots, capacity = serve["max_slots"], serve["slot_capacity"]
    moe = bool(config.is_moe)
    counts = {"return_attn_steps": True, "return_column_writes": True}
    if config.block == "gated_delta":
        counts["return_state_steps"] = True

    def decode(params, cache, tok, active):
        logits, new_cache, *rest = forward(
            params, tok[:, None], config, positions=cache.lengths[:, None], cache=cache,
            decode=True, attn_impl="auto", token_mask=active[:, None],
            return_moe_stats=moe, **counts,
        )
        return logits, new_cache, rest

    def chunk(params, row, tokens, offset, last):
        live = jnp.arange(tokens.shape[1])[None, :] <= last[:, None]
        return forward(
            params, tokens, config, cache=row, attn_impl="auto", prefill_offset=offset,
            last_positions=last, token_mask=live, return_moe_stats=moe,
        )

    length, wave = prefill_shapes(
        config, capacity, max_wave=serve.get("max_wave"), dropless_experts=moe
    )
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config, dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: init_cache(config, slots, capacity))
    row = jax.eval_shape(lambda: init_cache(config, wave, capacity))
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    texts = {
        "decode": jax.jit(decode).lower(*place((params, cache, i32(slots), active))).as_text(),
        "chunk_prefill": jax.jit(chunk).lower(
            *place((params, row, i32(wave, length), i32(), i32(wave)))
        ).as_text(),
    }
    assert "tpu_custom_call" in texts["decode"], "the gates are shut: no kernel in the decode step"
    out = {"prefill_shapes": [length, wave]}
    for role, text in texts.items():
        out[role] = hashlib.sha256(_KERNEL_BODY.sub("BODY", text).encode()).hexdigest()[:16]
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--write", action="store_true", help="record this tree's hashes")
    args = parser.parse_args()
    sys.path.insert(0, str(ROOT))
    with chip_gates():
        now = {name: hashes(name, serve) for name, serve in served().items()}
    print(json.dumps(now, indent=1))
    if args.write:
        RECORD.write_text(json.dumps(now, indent=1) + "\n")
        return 0
    recorded = json.loads(RECORD.read_text()) if RECORD.exists() else {}
    moved = sorted(name for name in now if now[name] != recorded.get(name))
    if moved:
        print("differs from the record:", ", ".join(moved), file=sys.stderr)
    return 1 if moved else 0


if __name__ == "__main__":
    raise SystemExit(main())
