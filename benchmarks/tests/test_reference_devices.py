"""The reference on the devices equals the reference on the host, bit for
bit, with its blocks dealt over four devices."""

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.tests.conftest import ROOT

CHECK = textwrap.dedent("""
    import json, sys
    import numpy as np
    sys.path.insert(0, %(root)r)
    import jax
    from benchmarks import reference as R, reference_devices as D
    from dpf_tpu import DPF
    n = 1 << 12
    rng = np.random.default_rng(12)
    table = rng.integers(-2 ** 31, 2 ** 31, (n, 16),
                         dtype=np.int64).astype(np.int32)
    rows = rng.integers(0, n, 6)
    k0, k1 = DPF(prf=2).gen_batch(rows, n,
                                  seeds=[rng.bytes(16) for _ in rows])
    k0, k1 = np.asarray(k0), np.asarray(k1)
    seen = set()
    put = jax.device_put

    def spy(x, sharding):
        out = put(x, sharding)
        if x.ndim == 3 and x.shape[-1] == 16:     # a round of row blocks
            seen.update((s.device.id, s.data.shape[0])
                        for s in out.addressable_shards)
        return out

    D.jax.device_put = spy
    bs = 6 * 2 ** 8                     # 16 blocks of 256 rows
    host0 = R.share(k0, table, "chacha20", block_seeds=bs)
    dev0 = D.share(k0, table, "chacha20", block_seeds=bs)
    dev1 = D.share(k1, table, "chacha20", block_seeds=bs)
    one = D.share(k0, table, "chacha20", devices=jax.devices()[:1])
    ctrl = D.share(k0, table, "chacha20", "float32", block_seeds=bs)
    rec = (dev0.astype(np.int64) - dev1).astype(np.int32)
    print(json.dumps({
        "devices": len(jax.devices()), "used": sorted(seen),
        "equal": bool(np.array_equal(dev0, host0)),
        "one_device_equal": bool(np.array_equal(one, host0)),
        "recovered": bool(np.array_equal(rec, table[rows])),
        "control_wrong_keys": int((ctrl != host0).any(axis=1).sum())}))
    """)


def test_device_reference_equals_host_over_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHECK % {"root": ROOT}],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    # each of the four devices took blocks of its own, one a round
    assert out == {"devices": 4, "used": [[i, 1] for i in range(4)],
                   "equal": True,
                   "one_device_equal": True, "recovered": True,
                   "control_wrong_keys": 6}


def test_device_reference_refuses_a_numpy_only_prf():
    import numpy as np
    import pytest
    from benchmarks import reference_devices as D
    with pytest.raises(ValueError, match="jax.numpy"):
        D.share(np.zeros((1, 524), np.int32), np.zeros((2, 16), np.int32),
                "aes128")
