"""Pallas-vs-XLA head-to-head for the grouped-aggregation hot path.

VERDICT r2 item 9: either ship a Pallas kernel where XLA's lowering
demonstrably loses, or record the measured case against it. The
candidate is the one-hot grouped sum (ops.aggregation._onehot_aggregate
— Q1's shape: ~8M rows, ~12 segments):

- ``xla_onehot``  — the engine's current composition: broadcast compare
  + masked sum, fused by XLA.
- ``pallas_onehot`` — hand-blocked VMEM kernel: rows stream through VMEM
  in (BLOCK, 128) tiles, an (nseg, 128) accumulator lives in VMEM across
  grid steps, per-segment masked sums unrolled on the VPU.

Both are timed with forced device_get sync, with the measured null
round trip subtracted (its size is not measured on the chip).
Numerical parity is asserted against a float64 numpy reference first.

Usage: python tools/pallas_groupby.py [--rows 8388608] [--nseg 12]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=8 * 1024 * 1024)
    ap.add_argument("--nseg", type=int, default=12)
    ap.add_argument("--block", type=int, default=2048)
    ap.add_argument(
        "--x64", action="store_true",
        help="run under the engine's jax_enable_x64=True config — "
        "reproduces the Mosaic 'failed to legalize func.return' compile "
        "failure (i64 leaks into the kernel), which is itself finding #1 "
        "against Pallas here: the engine's int64/float64 SQL semantics "
        "and Mosaic do not currently coexist",
    )
    args = ap.parse_args()

    if args.x64:
        import jax

        jax.config.update("jax_enable_x64", True)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, nseg, BLOCK = args.rows, args.nseg, args.block
    assert rows % 128 == 0
    M = rows // 128
    assert M % BLOCK == 0

    rng = np.random.RandomState(0)
    x_np = rng.rand(rows).astype(np.float32)
    g_np = rng.randint(0, nseg, rows).astype(np.int32)
    ref = np.array(
        [x_np[g_np == s].astype(np.float64).sum() for s in range(nseg)]
    )

    x = jnp.asarray(x_np)
    g = jnp.asarray(g_np)

    def xla_onehot(x, g):
        oh = g[:, None] == jnp.arange(nseg, dtype=jnp.int32)[None, :]
        return jnp.sum(jnp.where(oh, x[:, None], jnp.float32(0)), axis=0)

    x2 = x.reshape(M, 128)
    g2 = g.reshape(M, 128)

    def kernel(x_ref, g_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        xb = x_ref[:]
        gb = g_ref[:]
        partial = [
            jnp.sum(
                jnp.where(gb == jnp.int32(s), xb, jnp.float32(0)), axis=0
            )
            for s in range(nseg)
        ]
        out_ref[:] = out_ref[:] + jnp.stack(partial)

    def pallas_onehot(x2, g2):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((nseg, 128), jnp.float32),
            grid=(M // BLOCK,),
            in_specs=[
                pl.BlockSpec(
                    (BLOCK, 128), lambda i: (i, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (BLOCK, 128), lambda i: (i, 0),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (nseg, 128), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
        )(x2, g2)
        return jnp.sum(out, axis=1)

    def sync(y):
        return jax.device_get(y)

    def bench(fn, *a, iters=7):
        f = jax.jit(fn)
        out = sync(f(*a))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            sync(f(*a))
            ts.append(time.perf_counter() - t0)
        return out, min(ts)

    # null round trip: fetch a tiny precomputed value
    tiny = jnp.zeros((1,), jnp.float32)
    _, t_null = bench(lambda t: t + 1, tiny)

    out_x, t_x = bench(xla_onehot, x, g)
    out_p, t_p = bench(pallas_onehot, x2, g2)

    err_x = np.abs(np.asarray(out_x, np.float64) - ref).max() / ref.max()
    err_p = np.abs(np.asarray(out_p, np.float64) - ref).max() / ref.max()
    print(f"devices: {jax.devices()}  rows={rows} nseg={nseg}")
    print(f"null sync round trip:      {t_null * 1e3:8.2f} ms")
    print(
        f"XLA one-hot composition:   {t_x * 1e3:8.2f} ms "
        f"(-null: {(t_x - t_null) * 1e3:7.2f} ms)  max rel err {err_x:.2e}"
    )
    print(
        f"Pallas VMEM-blocked:       {t_p * 1e3:8.2f} ms "
        f"(-null: {(t_p - t_null) * 1e3:7.2f} ms)  max rel err {err_p:.2e}"
    )
    assert err_x < 1e-5 and err_p < 1e-5, "parity failure"
    hbm = rows * 8 / 1e9  # f32 data + i32 gid
    print(
        f"roofline (HBM {hbm:.2f} GB @ ~800 GB/s): {hbm / 800 * 1e3:.2f} ms"
    )


if __name__ == "__main__":
    main()
