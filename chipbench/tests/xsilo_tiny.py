"""The cross-silo cell at a size a CPU test run can hold."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402

BENCH = run.load_json(ROOT / "BENCHMARK.json")

#: ``minitron-8b-4l`` at a CPU size: q width 384 != d_model 256, GQA,
#: half rotary, relu2, LayerNorm1p; 4 layers
TINY_LM = dict(d_model=256, num_heads=6, num_kv_heads=2, head_dim=64,
               d_ff=512, vocab_size=512, num_layers=4)


def xsilo_context(seed: int = 5, seq: int = 64) -> "run.Context":
    """``minitron4l-xsilo-4chip`` shrunk to ``TINY_LM``, two local steps
    of 2 sequences of ``seq`` tokens a round."""
    tr = run.load_json(run.HERE / "traffic" / "xsilo-ama-fes.json")
    tr["generator"].update(vocab=TINY_LM["vocab_size"], seq=seq)
    tr["fl"].update(local_steps=2)
    ctx = run.Context(BENCH, "minitron4l-xsilo-4chip", seed, traffic=tr)
    ctx.config = {**ctx.config, **TINY_LM}
    return ctx
