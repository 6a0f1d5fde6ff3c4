"""The index scores and the selection against their roofline over the traced
rounds: the least time of every layer's call at *stated* work
(``benchmark/dsa_costs.py`` ``select_cost``: 2 x 16 x 64 FLOP a causal pair,
``qI``, ``kI`` and ``wI`` read once, what carries the selection written once;
once a training step, since the chosen set is kept and not made again), over
the device time under the scopes ``attn/dsa/index/scores`` and
``attn/dsa/select``. By scope and not by an op's name, from the shapes in the
program's ``dsa/call`` notes, so that it reads the same work whatever
implements the selection. A program without the scopes or the notes gives
None."""

from benchmark import dsa_reduce


def read(ctx):
    return dsa_reduce.select_roofline_pct(ctx)
