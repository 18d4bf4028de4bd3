"""The gated delta rule's decode kernel (ops/pallas_delta.py) against its
definition, ``models/delta.py::_step``, in interpret mode on the CPU: what it
computes for the rows it visits, and that it leaves everything else of the
stacked leaf bit for bit as it was. That Mosaic takes it at the served shape is
tests/test_aot_lowering.py's ``test_delta_decode_step_lowers``."""

import numpy as np
import pytest

import jax.numpy as jnp

from prime_tpu.models.delta import _step
from prime_tpu.ops import pallas_delta
from prime_tpu.ops.attention import delta_decode_work
from prime_tpu.ops.pallas_delta import delta_decode_step, delta_step_heads, delta_work_list

LIVE = {
    "every-row-live": [True, True, True, True, True],
    "some-rows-dead": [False, True, False, False, True],
    "every-row-dead": [False] * 5,
}


def _inputs(seed, layers, batch, heads, d_k, d_v):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.normal(size=(batch, heads, d_k))) * d_k**-0.5
    k = unit(rng.normal(size=(batch, heads, d_k)))
    v = rng.normal(size=(batch, heads, d_v))
    beta = rng.uniform(0.1, 0.9, size=(batch, heads))
    g = -rng.uniform(1e-3, 0.2, size=(batch, heads))
    leaf = rng.normal(size=(layers, batch, heads, d_k, d_v)) * 0.3
    return tuple(jnp.asarray(t, jnp.float32) for t in (leaf, q, k, v, beta, g))


def _check(leaf, layer, vectors, live, **kwargs):
    new, o = delta_decode_step(leaf, layer, *vectors, jnp.asarray(live), interpret=True, **kwargs)
    want_state, want_o = _step(leaf[layer], *vectors)
    new, o, old, live = np.asarray(new), np.asarray(o), np.asarray(leaf), np.asarray(live)
    np.testing.assert_allclose(new[layer][live], np.asarray(want_state)[live], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o[live], np.asarray(want_o)[live], rtol=1e-5, atol=1e-6)
    if live.any():  # the visited blocks did change
        assert not np.array_equal(new[layer][live], old[layer][live])
    # a row that belongs to nobody: its block as it was, bit for bit, and o zero
    assert np.array_equal(new[layer][~live], old[layer][~live])
    assert not o[~live].any()
    # and so the stack's other rows
    assert np.array_equal(np.delete(new, layer, 0), np.delete(old, layer, 0))


@pytest.mark.parametrize("dims", [(128, 128), (16, 16)], ids=["served-128x128", "tiny-16x16"])
@pytest.mark.parametrize("layer", [0, 2], ids=["stack-row-0", "stack-row-last"])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_kernel_is_the_step_for_live_rows_and_touches_nothing_else(live, layer, dims):
    leaf, *vectors = _inputs(3, 3, 5, 4, *dims)
    _check(leaf, layer, vectors, LIVE[live])


def test_a_row_in_several_head_blocks_and_a_callers_work_list(monkeypatch):
    """Heads that do not fit one step: a grid step a head block, the work list
    the caller's own (made once for all layers)."""
    # two heads' blocks as VMEM holds them: 8 lanes of values take a whole tile's 128
    monkeypatch.setattr(pallas_delta, "DECODE_VMEM_BUDGET", 4 * 2 * 16 * 128 * 4)
    assert delta_step_heads(6, 16, 8) == 2
    leaf, *vectors = _inputs(4, 2, 5, 6, 16, 8)
    live = LIVE["some-rows-dead"]
    _check(leaf, 1, vectors, live, work=delta_work_list(jnp.asarray(live)))


def test_heads_a_step_follow_the_shapes():
    assert delta_step_heads(32, 128, 128) == 32  # 2 MB a block, 8 MB held: one step a row
    assert delta_step_heads(64, 128, 128) == 32
    assert delta_step_heads(32, 256, 256) == 8
    assert delta_step_heads(4, 16, 16) == 4
    assert delta_step_heads(3, 1024, 1024) == 1
    # counted in whole (8, 128) tiles: 192 lanes take 256, so 30 heads of (96, 192) go 15 a step
    assert delta_step_heads(30, 96, 192) == 15 and delta_step_heads(30, 96, 256) == 15


def test_the_work_list_is_the_live_rows_in_order():
    rows, n = delta_work_list(jnp.asarray([False, True, True, False, True, False]))
    assert int(n) == 3 and rows.shape == (7,)
    assert np.asarray(rows).tolist() == [1, 2, 4, 4, 4, 4, 4]  # the tail repeats the last
    rows, n = delta_work_list(jnp.zeros((4,), bool))
    assert int(n) == 1  # one step, which finds its row dead and copies it through


@pytest.mark.parametrize("impl,interpret,dims,expected", [
    ("pallas", True, (16, 16), (2 * 3, 5 * 3)),
    ("pallas", False, (128, 128), (2 * 3, 5 * 3)),
    ("pallas", False, (96, 192), (2 * 3, 5 * 3)),  # values end inside a lane tile: padded in VMEM
    ("pallas", False, (12, 16), (0, 0)),      # keys end inside a sublane tile: plain XLA on a chip
    ("xla", True, (128, 128), (0, 0)),
    ("auto", True, (128, 128), (0, 0)),       # the CPU backend takes no kernel by itself
], ids=["interpret-any-shape", "whole-tiles", "padded-lanes", "ragged-tiles", "xla", "auto-on-cpu"])
def test_the_gate_is_the_decode_attentions_and_whole_tiles(monkeypatch, impl, interpret, dims, expected):
    if interpret:
        monkeypatch.setenv("PRIME_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PRIME_TPU_PALLAS_INTERPRET", raising=False)
    k_cache = jnp.zeros((1, 5, 2, 32, 256), jnp.float32)
    leaf = jnp.zeros((3, 5, 4, *dims), jnp.float32)
    work, steps = delta_decode_work(impl, k_cache, leaf, jnp.asarray(LIVE["some-rows-dead"]))
    assert (work is not None) == (expected != (0, 0))
    assert tuple(int(n) for n in np.asarray(steps)) == expected
