#!/usr/bin/env python3
"""The control of the grouped cell's limits: one run of a benchmark cell with
LambdaMART's pair pass in bfloat16 (score differences, rho, the pair weights
and the sums over pairs; ranks stay those of the float32 scores) and the
logged NDCG's gains and discounts rounded to bfloat16: the nearest precision
below the float32 the program states. It has to read
``correct: false`` (PERF.md section 2). Same arguments as ``benchmark/run.py``:

    python3 scripts/rank_bf16_control.py --workload mslr-ndcg.train-fused-grouped \\
        --seed <n> --seconds 20 --trace 0

The program has no option for this: the script swaps the block function in
``ops/ranking.py`` for the length of the run.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (sets T_PROCESS_START)


def install():
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import ranking

    def block_bf16(S, Y, W, valid, gains, max_dcg, scheme):
        # gains and max_dcg are the layout's, made at set-up through the
        # rounded `dcg_gain` and `dcg_discount` below
        assert scheme == "ndcg"
        low = jnp.bfloat16
        s = S.astype(low)
        rho = 1.0 / (1.0 + jnp.exp(s[:, :, None] - s[:, None, :]))
        prefer = (Y[:, :, None] > Y[:, None, :]) & valid[:, :, None] & valid[:, None, :]
        discount = ranking.dcg_discount(ranking.rank_descending(S, valid))
        g_low, d_low = gains.astype(low), discount.astype(low)
        delta = (
            jnp.abs(g_low[:, :, None] - g_low[:, None, :])
            * jnp.abs(d_low[:, :, None] - d_low[:, None, :])
            / max_dcg.astype(low)[:, None, None]
        )
        lam = jnp.where(prefer, rho * delta, 0).astype(low)
        hess = jnp.where(prefer, rho * (1 - rho) * delta, 0).astype(low)
        g = (-lam.sum(axis=2, dtype=low) + lam.sum(axis=1, dtype=low)).astype(jnp.float32)
        h = (hess.sum(axis=2, dtype=low) + hess.sum(axis=1, dtype=low)).astype(jnp.float32)
        h = jnp.maximum(h, 1e-16)
        return jnp.where(valid, g * W, 0.0), jnp.where(valid, h * W, 0.0)

    def through_bf16(fn):
        # an explicit rounding: XLA on the TPU drops a convert to bfloat16 and
        # back (`xla_allow_excess_precision`), it keeps this
        return lambda *args: jax.lax.reduce_precision(fn(*args), exponent_bits=8, mantissa_bits=7)

    ranking._lambdarank_block = block_bf16
    # the logged metric's gains and discounts (models/device_metrics.py reads
    # them through the module) in bfloat16 too
    ranking.dcg_gain = through_bf16(ranking.dcg_gain)
    ranking.dcg_discount = through_bf16(ranking.dcg_discount)


if __name__ == "__main__":
    install()
    sys.exit(run.main())
