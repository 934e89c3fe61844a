"""The reference against the program at a tiny Mistral-shaped size on the
CPU (grouped-query 4:1): full forward, then prefill-and-decode through the
engine, the router, the front door and the child load generator."""

import numpy as np

from perfbench import harness, reference
from perfbench.tests import tiny


def test_reference_matches_the_model_forward():
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(5)
    model = LlamaForCausalLM(harness.llama_config(tiny.MODEL))
    model.eval()
    ids = np.random.default_rng(0).integers(0, 128, 48)
    want = np.asarray(model(paddle.to_tensor(ids[None].astype(np.int32)))._data)[0]
    got = np.asarray(reference.logits(harness.reference_params(model),
                                      jnp.asarray(ids), tiny.MODEL))
    assert np.abs(got - want).max() < 2e-4


def test_serving_runner_against_the_reference():
    from perfbench.runners import serve_open_loop
    rec = serve_open_loop.run(tiny.ctx(tiny.SERVE, tiny.DOCQA, seconds=2.0,
                                       trace=1))
    assert rec["correct"] and rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["values"]["compiles_in_window"] == 0
    start, end = rec["counters"]["start"], rec["counters"]["end"]
    assert end["prefill_tokens_computed"] - start["prefill_tokens_computed"] \
        < end["prefill_tokens_requested"] - start["prefill_tokens_requested"]
    assert any(e["name"] == "serving.decode" for e in rec["spans"])


def test_training_runner_against_the_reference(monkeypatch):
    from perfbench.runners import train_steps
    # the chip's tolerance is for 4095 averaged positions at full width; 63
    # positions of a 64-wide model in bf16 average ten times less away
    monkeypatch.setattr(reference, "TRAIN_LOSS_TOL", 1e-3)
    rec = train_steps.run(tiny.ctx(tiny.TRAIN, tiny.STEPS, seconds=1.0))
    assert rec["correct"] and rec["failed"] == 0
    assert rec["values"]["tokens_per_s"] > 0
