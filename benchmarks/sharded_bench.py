"""Weak-scaling benchmark for the sharded CAM search subsystem.

Fixed rows/device, growing nv: every device count N holds the same
(BANKS_PER_DEV x ROWS) rows per device, so the dataset grows with the
mesh (the scale-out story: capacity bounded by the mesh, not one HBM).
Each sweep point reports the sharded wall time at N devices AND a
single-device (1-bank mesh) reference over the *same* N-shard dataset —
``speedup`` is therefore the cross-device parallelism win on identical
data, and ``match`` asserts the merge stayed bit-identical.

Every sweep point runs in this one process, on the devices JAX already
has: the point-code and ACAM rows for each device count in
``DEVICE_SWEEP`` up to ``--devices`` and up to ``len(jax.devices())``.
Nothing is spawned, so on a TPU host the process that holds the chips
measures them.  On the CPU, give JAX host devices before it starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.sharded_bench [--devices N]

A failing point raises, so the run exits non-zero.  Timings on the CPU
are Pallas interpret-mode wall clock, not device numbers.
"""
from __future__ import annotations

import sys
import time

BANKS_PER_DEV = 8     # nv shards resident per device
ROWS = 128            # R: rows per subarray (rows/device = 8 * 128)
COLS = 128            # C
NDIM = 256            # application dims -> nh = 2 segments
Q = 128               # query batch per search
DEVICE_SWEEP = (1, 2, 4)


def worker(n_devices: int) -> None:
    """One sweep point on ``n_devices`` local devices: the point-code
    (mcam/l2) row and the ACAM range-search row, both at fixed
    rows/device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (AppConfig, ArchConfig, CAMConfig, CircuitConfig,
                            DeviceConfig, ShardedCAMSimulator, SimConfig)
    from repro.launch.mesh import make_cam_mesh

    assert len(jax.devices()) >= n_devices, jax.devices()

    def timeit(f, n=7):
        for _ in range(2):
            jax.block_until_ready(f())
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    def one(cfg, stored, name: str) -> None:
        queries = jax.random.uniform(jax.random.PRNGKey(1), (Q, NDIM))
        if stored.ndim == 3:
            # ACAM: half the batch queries stored-row centers (guaranteed
            # exact range matches) so the parity bit compares real match
            # results, not two all-miss tensors
            centers = stored.mean(-1)
            rows = (jnp.arange(Q) * 7) % stored.shape[0]
            queries = jnp.where((jnp.arange(Q) % 2 == 0)[:, None],
                                centers[rows], queries)
        sharded = ShardedCAMSimulator(cfg, make_cam_mesh(n_devices))
        s_state = sharded.write(stored)
        t_n = timeit(lambda: sharded.query(s_state, queries))

        single = ShardedCAMSimulator(cfg, make_cam_mesh(1))
        o_state = single.write(stored)
        t_1 = timeit(lambda: single.query(o_state, queries))

        ia, _ = single.query(o_state, queries)
        ib, _ = sharded.query(s_state, queries)
        ok = bool((np.asarray(ia) == np.asarray(ib)).all())
        K = stored.shape[0]
        qps_n, qps_1 = Q / t_n, Q / t_1
        print(f"{name}_d{n_devices},{t_n * 1e6:.0f},"
              f"qps={qps_n:.0f}_qps_1dev={qps_1:.0f}_"
              f"speedup={t_1 / t_n:.2f}x_rows={K}_"
              f"rows_per_dev={BANKS_PER_DEV * ROWS}_match={ok}")

    K = n_devices * BANKS_PER_DEV * ROWS          # fixed rows/device
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))

    cfg = CAMConfig(
        app=AppConfig(distance="l2", match_type="best", match_param=3,
                      data_bits=3),
        arch=ArchConfig(h_merge="adder", v_merge="comparator"),
        circuit=CircuitConfig(rows=ROWS, cols=COLS, cell_type="mcam",
                              sensing="best"),
        device=DeviceConfig(device="fefet"),
        sim=SimConfig(use_kernel=True))
    one(cfg, jax.random.uniform(k1, (K, NDIM)), "kernel_cam_search_sharded")

    # ACAM: same grid geometry, [lo, hi] range rows, exact range match on
    # the fused range kernel's match-only path
    acam_cfg = CAMConfig(
        app=AppConfig(distance="range", match_type="exact", match_param=3,
                      data_bits=0),
        arch=ArchConfig(h_merge="and", v_merge="gather"),
        circuit=CircuitConfig(rows=ROWS, cols=COLS, cell_type="acam",
                              sensing="exact"),
        device=DeviceConfig(device="fefet"),
        sim=SimConfig(use_kernel=True))
    lo = jax.random.uniform(k2, (K, NDIM))
    ranges = jnp.stack([lo, lo + 0.05], axis=-1)
    one(acam_cfg, ranges, "kernel_acam_range_sharded")


def main(max_devices: int = 4) -> None:
    """Run every sweep point this process has devices for, in-process."""
    import jax

    have = len(jax.devices())
    for n in DEVICE_SWEEP:
        if n <= min(max_devices, have):
            worker(n)


if __name__ == "__main__":
    devs = 4
    if "--devices" in sys.argv:
        devs = int(sys.argv[sys.argv.index("--devices") + 1])
    main(devs)
