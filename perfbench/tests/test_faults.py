"""Drive a whole run (all but the look for a chip) with the timed path
broken underneath, and see ``correct`` come out false: once for each
fault a cell can have. Rehearsal sizes, CPU."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as bench   # noqa: E402

CHAT = "mistral-7b-v0.3-l16.chat-open"
CLASSIFY = "mistral-7b-v0.3-l16.classify-closed"
TRAIN = "internlm2-1.8b-l4.pretrain-4k"


def drive(cell, seconds="4"):
    code, result = bench.execute(["--workload", cell, "--seed", "3000000007",
                                  "--seconds", seconds, "--rehearse", "1"])
    assert code == bench.REHEARSAL_EXIT
    return result


@pytest.mark.parametrize("cell", [CHAT, CLASSIFY])
def test_sound_serving_run_is_correct(cell):
    result = drive(cell)
    assert result["correct"], result


@pytest.mark.parametrize("cell", [CHAT, CLASSIFY])
def test_token_altered_where_it_is_produced(cell, monkeypatch):
    from paddle_tpu.inference.serving import LlamaServingEngine
    emit = LlamaServingEngine._emit

    def wrong(self, req, token):
        if len(req.output_ids) % 3 == 0:
            token = (int(token) + 1) % self.model.config.vocab_size
        return emit(self, req, token)

    monkeypatch.setattr(LlamaServingEngine, "_emit", wrong)
    result = drive(cell)
    assert not result["correct"]
    assert result["checks"]["gap_max"]["value"] \
        > result["checks"]["gap_max"]["limit"]


def test_sound_training_run_is_correct():
    result = drive(TRAIN, "2")
    assert result["correct"], result


def test_step_that_returns_its_state_unchanged(monkeypatch):
    import paddle_tpu as paddle
    real = paddle.optimizer.AdamW.step
    calls = {"n": 0}

    def lazy(self):
        calls["n"] += 1
        if calls["n"] == 1:         # the eager warm-up builds the state
            return real(self)

    monkeypatch.setattr(paddle.optimizer.AdamW, "step", lazy)
    result = drive(TRAIN, "2")
    assert not result["correct"]
    assert result["checks"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch):
    from paddle_tpu.models import LlamaForCausalLM
    real = LlamaForCausalLM.forward

    def half(self, input_ids, labels=None, position_ids=None):
        if labels is not None and input_ids.shape[0] > 1:
            k = input_ids.shape[0] // 2
            return real(self, input_ids[:k], labels[:k], position_ids)
        return real(self, input_ids, labels, position_ids)

    monkeypatch.setattr(LlamaForCausalLM, "forward", half)
    result = drive(TRAIN, "2")
    assert not result["correct"]
    assert result["checks"]["grad_gap"]["value"] \
        > result["checks"]["grad_gap"]["limit"]
