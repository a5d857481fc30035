"""The comparison fails what it must: the control (the reference in
bfloat16 in the program's place), and a run whose timed path is broken
underneath in each way a training cell on one chip can be."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import chipbench_tiny  # noqa: E402

from chipbench import control  # noqa: E402


def _fails(numbers: dict, limits: dict) -> list:
    return [k for k in limits if not numbers[k] <= limits[k]]


def test_control_fails_the_limits():
    ctx = chipbench_tiny.paper_context(6)
    r = control.readings(ctx, True)
    assert not _fails(r["program"], ctx.limits)
    assert _fails(r["control"], ctx.limits)
    for fault, numbers in r["faults"].items():
        assert _fails(numbers, ctx.limits), fault


def _unchanged_state(monkeypatch):
    from repro.core.strategies.ama import AMAStrategy
    monkeypatch.setattr(AMAStrategy, "fused_server_update",
                        lambda self, t, prev, cp, sched, aux: (prev, aux))


def _half_batch(monkeypatch):
    from repro.models import cnn
    loss = cnn.loss_fn

    def half(params, cfg, batch):
        b = batch["label"].shape[0] // 2
        return loss(params, cfg, {k: v[:b] for k, v in batch.items()})
    monkeypatch.setattr(cnn, "loss_fn", half)


def _label_altered(monkeypatch):
    from repro.exec import engine
    stage = engine.stage_chunk

    def altered(*args, **kwargs):
        out = stage(*args, **kwargs)
        out["label"][:, 0] = (out["label"][:, 0] + 1) % 10
        return out
    monkeypatch.setattr(engine, "stage_chunk", altered)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _label_altered],
                         ids=["unchanged_state", "half_batch",
                              "label_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    out = chipbench_tiny.measure(chipbench_tiny.paper_context(8))
    assert out["correct"] is False, out["checks"]
