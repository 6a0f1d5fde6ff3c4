"""Worker process for test_splitnn_real_processes: ONE SplitNN client rank
over the native shm ring against the parent process's server — the
reference's actual process model (split_nn/client.py runs per-process).
Run as: ``python tests/_splitnn_worker.py <job> <rank> <world> <batches.npz>``

The bottom/top module definitions mirror tests/test_comm_pipelines._Bottom/
_Top exactly; parameters come from the server's INIT message, so any
definition drift fails the bit-equality assertion loudly.
"""

import sys


def main(job: str, rank: int, world: int, npz_path: str) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    # the compile cache directory arrives as $JAX_COMPILATION_CACHE_DIR from
    # the parent test (the suite's own string), which jax reads by itself
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    import numpy as np

    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from fedml_tpu.algorithms.splitnn import SplitNN
    from fedml_tpu.algorithms.splitnn_dist import SplitNNClientManager
    from fedml_tpu.comm.shm import ShmCommManager

    class _Bottom(nn.Module):
        hidden: int = 12

        @nn.compact
        def __call__(self, x, train: bool = False):
            return nn.relu(nn.Dense(self.hidden)(x.astype(jnp.float32)))

    class _Top(nn.Module):
        classes: int = 4

        @nn.compact
        def __call__(self, acts, train: bool = False):
            return nn.Dense(self.classes)(acts)

    data = np.load(npz_path)
    batches = {k: jnp.asarray(data[k]) for k in data.files}
    split = SplitNN(_Bottom(), _Top(), optax.sgd(0.2), optax.sgd(0.2))
    comm = ShmCommManager(job, rank, world)
    mgr = SplitNNClientManager(comm, rank, world, split, batches)
    mgr.run()  # blocks until the server's FINISHED message
    comm.cleanup()  # close AND unlink this rank's /dev/shm ring


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
