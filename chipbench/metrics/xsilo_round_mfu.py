"""xsilo_round_mfu: ``round_mfu`` read in the cross-silo cell, whose
driver counts the rounds' required FLOPs with chipbench/flops_lm.py. It
is a metric of its own only because ``round_mfu``'s entry lists the
cells it is read in; the reading is ``round_mfu``'s."""
from chipbench import run

read = run.load_module(run.HERE / "metrics" / "round_mfu.py").read
