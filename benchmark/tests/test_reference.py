"""The plain reference against the program's model at tiny-test width on the
CPU, and the near-argmax check: it passes on what the program computes and
fails when the weights are rounded to int8 (the control)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check
from benchmark.harness.manifest import load_json
from benchmark.reference import qwen3_dense as reference

CONFIG = load_json("configs", "qwen3-4b")
TINY = CONFIG["rehearsal"]


@pytest.fixture(scope="module")
def program():
    from prime_tpu.models import get_config
    from prime_tpu.models.llama import forward, init_params

    config = get_config(TINY["preset"])
    params = init_params(jax.random.PRNGKey(CONFIG["assumed"]["weights_seed"]), config, dtype=jnp.bfloat16)
    return config, params, forward


def test_the_reference_makes_the_weights_the_program_makes(program):
    _, params, _ = program
    weights = reference.make_weights(TINY["model"], CONFIG["assumed"]["weights_seed"], jax.local_devices()[:1])
    assert np.array_equal(np.asarray(weights["embed"], dtype=np.float32),
                          np.asarray(params["embed"], dtype=np.float32))
    # one jitted call here, leaf by leaf there: a few elements in a hundred
    # thousand round the other way, by one bfloat16 step
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        ours = np.asarray(weights[name], dtype=np.float32)
        theirs = np.asarray(params["layers"][name], dtype=np.float32)
        differ = ours != theirs
        assert differ.mean() < 1e-3, name
        assert np.all(np.abs(ours - theirs)[differ] <= np.abs(theirs[differ]) * 2.0 ** -7), name


def _sequences(program, count=4, prompt_len=40, answer_len=24):
    """Greedy tokens of the program's own full forward (no cache) in bfloat16."""
    config, params, forward = program
    rng = np.random.default_rng(0)
    out = []
    for _ in range(count):
        ids = rng.integers(128, config.vocab_size, size=prompt_len).tolist()
        answer = []
        for _ in range(answer_len):
            tokens = jnp.asarray([ids + answer], dtype=jnp.int32)
            logits = forward(params, tokens, config)
            logits = logits[0] if isinstance(logits, tuple) else logits
            answer.append(int(jnp.argmax(logits[0, -1])))
        out.append({"prompt": ids, "answer": answer, "max_tokens": answer_len})
    return out


def test_near_argmax_passes_on_the_program_and_fails_on_int8(program):
    weights = reference.make_weights(TINY["model"], CONFIG["assumed"]["weights_seed"], jax.local_devices()[:1])
    seen = check.gaps({"reference": "qwen3_dense", "model": TINY["model"]}, weights, _sequences(program),
                      pad_to=64, answer_pad=32, eos_id=2, control="int8")
    served, control = seen["served"], seen["control"]
    # bfloat16 against float32 at this width: a served token is the reference's
    # best or within a rounding of it; int8 weights move the logits far more
    assert served["gap_mean"] < 2e-3 and served["gap_max"] < 0.05
    assert control["gap_mean"] > 3 * served["gap_mean"]
    assert check.verdict([("logit_gap_mean", served["gap_mean"], 2e-3)])
    assert not check.verdict([("logit_gap_mean", control["gap_mean"], 2e-3)]) or \
        not check.verdict([("logit_gap_max", control["gap_max"], 0.05)])


def test_a_short_stream_must_have_ended_on_eos(program):
    weights = reference.make_weights(TINY["model"], CONFIG["assumed"]["weights_seed"], jax.local_devices()[:1])
    seq = _sequences(program, count=1)[0]
    cut = {**seq, "answer": seq["answer"][:10]}   # stopped early, and not on eos
    seen = check.gaps({"reference": "qwen3_dense", "model": TINY["model"]}, weights, [cut],
                      pad_to=64, answer_pad=32, eos_id=2)
    assert seen["served"]["gap_max"] > 0.5
