"""``correct`` has to be able to come out false.

- The control: the reference in int8, put in the program's place, fails
  the limits (at the rehearsal size, where the program computes in
  float32 and the limits are float32's; on the chip the same comparison
  was read at the cells' own sizes, PERF.md section 2).
- A run driven end to end (only the look for a chip is skipped, which is
  what ``--rehearsal`` does) with the timed path broken underneath: a
  token altered where the engine books it, a train step that returns its
  state unchanged. Each has to end with ``correct`` false.
"""
import json

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import build, check
from benchmarks.harness.spec import Spec
from benchmarks.reference import model as ref
from pbs_tpu.models.serving import ContinuousBatcher

SEED = 2 ** 31 + 17


def last_json(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def rehearse(cell: str, capsys, seconds="2") -> dict:
    assert run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                     seconds, "--trace", "0", "--rehearsal"]) == 0
    return last_json(capsys)


def test_sound_run_is_correct(capsys):
    out = rehearse("colo-train-serve", capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"rehearsal_train_tokens_per_s",
                                   "rehearsal_output_tokens_per_s",
                                   "rehearsal_setup_s"}


def test_altered_token_is_not_correct(capsys, monkeypatch):
    emit = ContinuousBatcher._emit

    def wrong(self, slot, tok):
        return emit(self, slot, (tok + 1) % self.cfg.vocab)

    monkeypatch.setattr(ContinuousBatcher, "_emit", wrong)
    assert rehearse("serve-chat-steady", capsys)["correct"] is False


def test_step_that_returns_its_state_is_not_correct(capsys, monkeypatch):
    def lazy(self, st):
        self.steps += 1
        _, m = self.step(jax_copy(st), self.rows[0])
        if len(self.first_losses) < 3:
            self.first_losses.append(m["loss"])
        return st, {"tokens": m["tokens"]}

    import jax

    def jax_copy(tree):  # the real step donates its argument
        return jax.tree.map(lambda x: x + 0 if hasattr(x, "shape") else x,
                            tree)

    monkeypatch.setattr(build.Trainer, "_step_fn", lazy)
    assert rehearse("train-solo", capsys)["correct"] is False


def test_int8_control_fails_the_training_limits():
    spec = Spec()
    c = run.overlay(spec.config("internlm2-1.8b"),
                    spec.config("internlm2-1.8b")["rehearsal"])
    tr = c["train"]
    rows = [np.random.default_rng(i).integers(
        0, c["vocab_size"], (tr["batch"], tr["seq"]), dtype=np.int32)
        for i in range(3)]
    low = ref.train_readings(c, SEED, tr["num_hidden_layers"], rows,
                             tr["learning_rate"], quant=True)
    low["rows"] = rows
    readings = check.training_readings(ref, c, SEED, low)
    ok, _ = check.judge(readings, c["check"]["training"])
    assert not ok
    assert readings["grad_sketch_gap"] > 3 * c["check"]["training"][
        "grad_sketch_gap"]


@pytest.mark.parametrize("name", ["internlm2-1.8b", "mistral-7b-v0.3"])
def test_int8_control_fails_the_serving_limits(name):
    """The tokens the int8 reference puts first lie further below the
    float32 reference's best than the limit allows."""
    spec = Spec()
    c = run.overlay(spec.config(name), spec.config(name)["rehearsal"])
    rng = np.random.default_rng(5)
    sample = [{"prompt": rng.integers(1, c["vocab_size"], 12,
                                      dtype=np.int32),
               "tokens": [int(t) for t in rng.integers(
                   1, c["vocab_size"], 24)]} for _ in range(check.SAMPLE)]
    got = check.serving_readings(ref, c, SEED, sample, 24, control=True)
    as_program = {"served_gap_max": got["control_gap_max"],
                  "served_gap_mean": got["control_gap_mean"]}
    ok, _ = check.judge(as_program, c["check"]["serving"])
    assert not ok
