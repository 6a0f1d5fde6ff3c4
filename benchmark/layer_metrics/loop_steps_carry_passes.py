"""How many times over the local step's carry (variables, optimizer state and
key, the bytes of the program's ``loop/carry`` note) is read and written at
the chip's memory bandwidth in the ``loop_steps_time_pct`` ops of one step.
About 1 is one copy a step at bandwidth; well over 1, the carry is moved
more than once or slowly. None without the names or the notes
(``benchmark/loop_reduce.py``)."""

from benchmark import loop_reduce


def read(ctx):
    return loop_reduce.carry_passes(ctx)
