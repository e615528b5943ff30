"""Worker process for the two-process jax.distributed test.

Usage: python multihost_worker.py <rank> <port>

Forces a 2-virtual-device CPU platform, joins the 2-process cluster at
127.0.0.1:<port>, builds the global ("batch", "table") mesh over all 4
global devices, runs one tiny table-sharded DPF evaluation, checks
recovery, and prints MULTIHOST_OK <rank>.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dpf_tpu.utils.hermetic import force_cpu_mesh  # noqa: E402

# verify=False: the backend must stay uninitialized until
# jax.distributed.initialize has run (it refuses to start otherwise)
force_cpu_mesh(2, verify=False)


def main():
    rank = int(sys.argv[1])
    port = sys.argv[2]

    import numpy as np

    import jax

    from dpf_tpu.core import expand, keygen
    from dpf_tpu.parallel import multihost, sharded

    ok = multihost.initialize("127.0.0.1:%s" % port, 2, rank)
    assert ok and multihost.is_initialized()
    assert jax.default_backend() == "cpu"
    assert multihost.initialize() is True  # idempotent re-entry
    pi, pc = multihost.process_info()
    assert (pi, pc) == (rank, 2), (pi, pc)

    mesh = multihost.global_mesh(n_batch=1)
    assert mesh.devices.size == 4, mesh.devices  # 2 procs x 2 devices
    assert mesh.shape["table"] == 4

    n, method = 256, 2  # ChaCha
    table = np.arange(n * 2, dtype=np.int32).reshape(n, 2)
    tdev = sharded.place_table(table, mesh)
    k0, k1 = keygen.generate_keys(42, n, b"multihost", method)
    cw1, cw2, last = expand.pack_keys([k0, k1])
    out = sharded.eval_sharded(cw1, cw2, last, tdev, depth=8,
                               prf_method=method, chunk_leaves=32,
                               mesh=mesh)
    out = np.asarray(jax.device_get(out))
    rec = (out[0] - out[1]).astype(np.int32)
    assert (rec == table[42]).all(), rec
    print("MULTIHOST_OK %d" % rank, flush=True)


if __name__ == "__main__":
    main()
