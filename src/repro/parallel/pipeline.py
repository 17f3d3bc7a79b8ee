"""GPipe-style pipeline parallelism over a mesh axis (opt-in layer).

Stages hold contiguous layer groups; microbatches stream through a
``shard_map`` over the ``stage`` axis with ``ppermute`` moving activations to
the next stage each tick.  The schedule is the classic (n_micro + n_stages-1)
-tick wavefront: tick t has stage s working on microbatch (t - s) — bubbles
at the ends, steady-state utilization n_micro / (n_micro + n_stages - 1).

This is the building block for depth-wise scaling past what FSDPxTP carries;
it is exercised by tests/test_pipeline.py on an 8-device host mesh and kept
off the default dry-run cells (the assigned meshes are 2D data x model).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    mesh: Mesh,
    stage_fn: Callable,  # (stage_params, x) -> x, applied by every stage
    n_micro: int,
    *,
    axis: str = "stage",
):
    """Returns fn(stacked_stage_params, x_microbatched) -> y.

    stacked_stage_params: pytree with leading dim n_stages (sharded on
    ``axis``); x_microbatched: (n_micro, mb, ...) replicated input; output
    (n_micro, mb, ...) — the result of all stages applied in order.
    """
    n_stages = mesh.shape[axis]

    def local(params_l, xs):  # params_l: (1, ...) slice; xs: (n_micro, mb, d)
        params_l = jax.tree.map(lambda a: a[0], params_l)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        mb = xs.shape[1:]
        buf = jnp.zeros_like(xs)  # outputs parking (on the last stage)
        carry_in = jnp.zeros(mb, xs.dtype)  # activation arriving this tick

        def tick(state, t):
            carry_in, buf = state
            # stage 0 injects microbatch t; others use the permuted carry
            inject = jnp.where(
                (t >= 0) & (t < n_micro), xs[jnp.clip(t, 0, n_micro - 1)], 0.0
            )
            x_in = jnp.where(stage == 0, inject, carry_in)
            y = stage_fn(params_l, x_in)
            # last stage parks finished microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            park = (stage == n_stages - 1) & (out_idx >= 0) & (out_idx < n_micro)
            buf = jax.lax.cond(
                park,
                lambda b: jax.lax.dynamic_update_slice(
                    b, y[None], (jnp.clip(out_idx, 0, n_micro - 1),) + (0,) * len(mb)
                ),
                lambda b: b,
                buf,
            )
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            carry_out = jax.lax.ppermute(y, axis, perm)
            return (carry_out, buf), None

        (_, buf), _ = jax.lax.scan(
            tick, (carry_in, buf), jnp.arange(n_ticks)
        )
        # only the last stage parked outputs; psum replicates them everywhere
        return jax.lax.psum(buf, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
