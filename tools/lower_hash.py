"""sha256 of a device program of ``FedSim``, lowered for a described TPU v5e.

No chip and no compile: the program is traced and lowered to StableHLO on the
host, for one chip of a ``v5e:2x2`` that is described and not attached (the
``on-chip-measurement`` guide, 2), so the Mosaic kernels and the rolled scans
are the chip's and not the CPU's. Two trees whose hashes agree hand XLA the
same program under the same ``op_name``s: a host-side change of the engine or
a move inside a model is shown to have moved nothing before any chip call.

    python tools/lower_hash.py <root> <cell> ... [--program round,block:<n>,eval]

``<root>`` is the tree whose ``fedml_tpu`` and ``benchmark`` are imported (a
``git archive`` of another commit, or ``.``); the cells are ``BENCHMARK.json``'s.
One JSON line a cell and program (``round`` unless told). ``sha256_stripped`` covers every operation with its
name stack (``fed/...``, ``loop/...``) and leaves out what moves when nothing
has: file names and line numbers, and the Mosaic kernels' serialized payloads,
which carry their own. ``sha256_whole`` is the plain text, payloads in: equal
only while no kernel's source has moved a line.

The program is read off ``FedSim`` under the names its other readers use
(``_gather_round_fn``, ``_get_block_fn(n)``, ``_eval_gather_fn``) with the specs
and donations the engine gave it, on the arguments the engine's own staging
makes; ``FedSim`` is built on the described chip's mesh with ``FedSim._put``
returning shapes, because nothing can be placed on a device that is not there.
It covers what the one-chip cells run: a replicated model on a one-chip client
mesh with the dataset resident.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
import types


def described_chip_mesh(device=None):
    """A one-chip client mesh of a described v5e (``device``: one of a
    topology the caller has described already)."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from fedml_tpu.parallel import mesh as meshlib

    if device is None:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        device = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    return Mesh(np.array([device]), (meshlib.CLIENT_AXIS,))


def as_on_the_chip(setattr_):
    """Answer "which backend?" as the chip would, wherever this tree's code
    asks while it traces: the flash kernels go to Mosaic and the scans stay
    rolled. ``setattr_(object, name, value)`` does the patching
    (``monkeypatch.setattr`` in a test, ``setattr`` in a process that ends)."""
    import jax

    from fedml_tpu.core import scan as scanlib

    for name, module in list(sys.modules.items()):
        if name.startswith("fedml_tpu.") and hasattr(module, "_interpret_on"):
            setattr_(module, "_interpret_on", lambda platform: False)
    setattr_(scanlib, "jax", types.SimpleNamespace(
        default_backend=lambda: "tpu", lax=jax.lax, tree=jax.tree))


def put_shapes(self, value, sharding):
    """Stands in for ``FedSim._put``: the shapes of what it would place."""
    import jax
    import numpy as np

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=sharding), value)


def lower_program(sim, program: str):
    """``round`` (the device-gathered round program), ``block:<n>`` (n rounds
    in one program) or ``eval`` (the pooled eval over the resident dataset),
    lowered, of a ``FedSim`` built on :func:`described_chip_mesh` with
    :func:`put_shapes` for its ``_put``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedml_tpu.core import rng as rnglib

    if sim._spmd or sim._per_client or sim._pack or not sim._on_device:
        raise ValueError("lower_hash reads the programs of a replicated model on a "
                         "client mesh with the dataset resident")
    rep = NamedSharding(sim.mesh, P())

    def shapes(tree):  # of real host or CPU arrays, as the chip would hold them replicated
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), tree)

    variables = shapes(jax.eval_shape(sim.init_variables))
    state = shapes(jax.eval_shape(sim.aggregator.init_state, variables))
    root = rnglib.root_key(sim.config.seed)
    if program == "round":
        *staged, rkey = sim.stage_round(0, root)
        fn = sim._gather_round_fn.fn
        args = (variables, state, sim._dataset, *staged, shapes(rkey))
        if sim._mean_in_carry:  # and a dead model's buffers to sum into
            args += (variables,)
    elif program.startswith("block:"):
        n = int(program.split(":")[1])
        *staged, rngs = sim._stage_block(0, n, root)
        fn, args = sim._get_block_fn(n).fn, (variables, state, sim._dataset, *staged, shapes(rngs))
    elif program == "eval":
        fn, args = sim._eval_gather_fn, (variables, sim._dataset, sim._train_eval_idx)
    else:
        raise ValueError(f"unknown program {program!r} (round, block:<n> or eval)")
    return fn.lower(*args)


_PAYLOAD = re.compile(r'backend_config = "[^"]*"')


def strip(text: str) -> str:
    """``lower_program(...).as_text(debug_info=True)`` with every location reduced to
    the name it carries: ``loc(#loc7)`` becomes ``loc("fed/opt/mul")``, file
    names and line numbers and the ``#loc`` table go, and so do the Mosaic
    payloads."""
    names = dict(re.findall(r'^(#loc\d*) = loc\("([^"]*)"', text, re.M))
    body = "\n".join(line for line in text.splitlines() if not line.startswith("#loc"))
    body = _PAYLOAD.sub('backend_config = "..."', body)
    body = re.sub(r'"[^"]*":\d+:\d+(?: to \d*:\d+)?', '"file"', body)
    return re.sub(r"#loc\d*", lambda m: '"%s"' % names.get(m.group(0), ""), body)


def sha256(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def cell_sim(root: str, name: str, mesh):
    """The cell's ``FedSim`` as ``benchmark/run.py`` ``build_sim`` makes it,
    on ``mesh``."""
    from benchmark import run as benchrun
    from fedml_tpu.sim.engine import FedSim

    cell = benchrun.load_cell(name, root)
    job = cell["family"].build(cell["config"], cell["traffic"], 1)
    return FedSim(job["trainer"], job["train"], job["test"], job["sim_config"], mesh=mesh)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("root")
    parser.add_argument("cells", nargs="+")
    parser.add_argument("--program", default="round", help="comma-separated: round, block:<n>, eval")
    args = parser.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.chdir(root)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from fedml_tpu.sim.engine import FedSim

    FedSim._put = put_shapes
    mesh = described_chip_mesh()
    for name in args.cells:
        sim = cell_sim(root, name, mesh)
        as_on_the_chip(setattr)  # once the cell's modules are all imported
        for program in args.program.split(","):
            lowered = lower_program(sim, program)
            named, whole = lowered.as_text(debug_info=True), lowered.as_text()
            print(json.dumps({
                "cell": name, "program": program, "root": root,
                "tpu_custom_calls": whole.count("tpu_custom_call"), "chars": len(whole),
                "sha256_stripped": sha256(strip(named)), "sha256_whole": sha256(whole),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
