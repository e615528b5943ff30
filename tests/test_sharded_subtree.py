"""The subtree kernel under a mesh: each of four (virtual CPU) devices
runs the Pallas subtree kernel, in Mosaic's TPU interpreter, over its
own rows of a 2^12 x 16 table, and the shares meet in a psum.  A file of
its own: each case compiles an interpreted kernel (~15 s), so the
parallel test run can place it beside ``test_sharded.py``."""

import numpy as np
import pytest

from dpf_tpu import DPF
from dpf_tpu.parallel import sharded


@pytest.fixture(scope="module")
def eight_devices():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return jax.devices()


def _four_chip_mesh():
    import jax
    return sharded.make_mesh(n_table=4, devices=jax.devices()[:4])


@pytest.mark.parametrize("batch", [8, 13])
@pytest.mark.parametrize("prf", [DPF.PRF_CHACHA20, DPF.PRF_SALSA20])
def test_mesh_subtree_kernel_matches_cpu_and_single_chip(eight_devices, prf,
                                                         batch):
    """The subtree kernel per shard (Mosaic's interpreter) over four
    devices, 2^12 x 16 rows: two subtrees of nine kernel levels a shard,
    13 keys not a multiple of the key tile.  Server 0's shares equal
    the host oracle and the one-chip xla path bit for bit, and the
    dispatch span names the kernel and its one register-tiled level."""
    from jax.experimental.pallas import tpu as pltpu

    from dpf_tpu.obs import tracer
    n = 1 << 12
    rng = np.random.default_rng(prf * 100 + batch)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 16),
                         dtype=np.int64).astype(np.int32)
    dpf = DPF(prf=prf)
    keys = list(dpf.gen_batch(rng.integers(0, n, batch), n)[0])
    dpf.eval_init(table)
    one_chip = np.asarray(dpf.eval_tpu(keys))
    assert (one_chip == np.asarray(dpf.eval_cpu(keys))).all()
    with pltpu.force_tpu_interpret_mode():
        srv = sharded.ShardedDPFServer(table, _four_chip_mesh(),
                                       prf_method=prf, batch_size=batch,
                                       kernel_impl="pallas",
                                       chunk_leaves=512)
        kn = srv.resolved_eval_knobs(batch)
        assert (kn["kernel_impl"], kn["chunk_leaves"]) == ("pallas", 512)
        assert srv.shard_rows // kn["chunk_leaves"] == 2
        assert srv.table_sharded.dtype == np.int8   # no int32 table placed
        t = tracer.enable()
        try:
            got = srv.eval(keys)
            spans = [e for e in t.events()
                     if e["name"] == "mesh_eval.dispatch"]
        finally:
            tracer.disable()
    assert (got == one_chip).all()
    assert [s["attrs"] for s in spans] == [{"kernel": "pallas",
                                            "tiled_levels": 1}]
