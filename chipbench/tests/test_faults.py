"""Whole runs with the timed path broken underneath: `correct` must come
out false for each fault the cell can have (one chip: no exchange between
chips to leave out)."""
import pytest

from chipbench import common
from conftest import drive, small_fl_traffic, small_serve_traffic


def _serve_run(monkeypatch, qwen_smoke, broken):
    from repro.launch.serve_loop import PagedServeLoop
    monkeypatch.setattr(PagedServeLoop, "_decode_impl",
                        broken(PagedServeLoop._decode_impl))
    return drive("serve_closed", qwen_smoke,
                 small_serve_traffic("chat-closed8"), seconds=3.0)


def test_sound_serve_run_is_correct(qwen_smoke):
    assert drive("serve_closed", qwen_smoke,
                 small_serve_traffic("chat-closed8"), seconds=3.0)["correct"]


def test_decode_step_that_keeps_its_state(monkeypatch, qwen_smoke):
    def broken(step):
        def keep(self, params, pages, bt, tokens, positions):
            nxt, _ = step(self, params, pages, bt, tokens, positions)
            return nxt, pages                  # K/V of the step never written
        return keep
    assert not _serve_run(monkeypatch, qwen_smoke, broken)["correct"]


def test_decode_step_that_alters_a_token(monkeypatch, qwen_smoke):
    def broken(step):
        def alter(self, params, pages, bt, tokens, positions):
            nxt, pages = step(self, params, pages, bt, tokens, positions)
            V = self.model.cfg.vocab_size
            return nxt.at[0].set((nxt[0] + 1) % V), pages
        return alter
    assert not _serve_run(monkeypatch, qwen_smoke, broken)["correct"]


FL_CFG = common.load_json(common.ROOT / "chipbench/configs/"
                          "flight-cnn-cifar.json")


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fl_round_with_a_fault(monkeypatch, fault):
    from repro.core import federated
    from repro.core.client import LocalTrainer
    if fault == "state_unchanged":
        def train_cohort(self, params, images, labels, keys, epochs):
            return federated.stack_islands(params, len(keys))
        monkeypatch.setattr(LocalTrainer, "train_cohort", train_cohort)
    else:
        loss = LocalTrainer._loss

        def half(self, params, images, labels):
            n = images.shape[0] // 2
            return loss(self, params, images[:n], labels[:n])
        monkeypatch.setattr(LocalTrainer, "_loss", half)
    res = drive("fl_sync", FL_CFG, small_fl_traffic("cohort256"),
                seconds=1.0)
    assert not res["correct"]
    if fault == "state_unchanged":
        assert res["checks"]["local_gap"][0] == pytest.approx(1.0)
