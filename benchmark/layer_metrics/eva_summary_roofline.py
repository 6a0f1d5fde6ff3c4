"""The chunk summaries against their roofline over the traced rounds: the
least time of every layer's call, forward and backward, at *stated* traffic
(``benchmark/eva_costs.py`` ``summary_cost``: k and v read once and the two
``[heads, T / chunk, d]`` summaries written forward; k, v and the summaries'
gradients read and the gradients of k and v written backward; a recomputed
forward counts once, it is no work the model asks for), over the device time
under the scope ``attn/eva/summary``. By scope and not by an op's name, from
the shapes in the program's ``eva/call`` notes, so that it reads the same work
whatever implements the summaries. A program without the scope or the notes
gives None."""

from benchmark import eva_reduce


def read(ctx):
    return eva_reduce.summary_roofline_pct(ctx)
