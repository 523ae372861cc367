"""GPipe-style pipeline parallelism (pp) over a ``'stage'`` mesh axis.

The reference framework scales torch consumers with data-parallel sharding only; the
TPU-native parallelism families here add pipeline parallelism the XLA way — one jitted
SPMD program, no per-stage processes or hand-written schedules:

- **Stacked stage parameters.** Per-stage parameter pytrees are stacked along a leading
  stages axis (:func:`stack_stage_params`) and sharded ``PartitionSpec('stage', ...)``
  (:func:`stage_partition_specs`); inside ``shard_map`` each device holds exactly its
  stage's slice.
- **ppermute schedule.** Microbatches stream through a ``lax.scan`` of
  ``n_micro + n_stages - 1`` ticks (the classic GPipe schedule, bubble ``n_stages-1``);
  every tick applies the local stage and shifts activations to the next stage with
  ``lax.ppermute`` over ICI.
- **Differentiable end to end.** ``scan`` and ``ppermute`` have exact transposes, so
  ``jax.grad`` through the pipeline yields the pipeline-parallel backward pass — no
  manual backward schedule, matching how XLA wants pipelines expressed.

``stage_fn`` must be shape- and dtype-preserving (activations circulate through a fixed
buffer), which transformer blocks are. Inputs are replicated over the stage axis and may
be sharded over other mesh axes (e.g. ``xs_spec=P(None, 'data')`` for dp+pp); GPipe
holds all microbatches resident anyway, so the replication does not change the memory
order.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def stack_stage_params(stage_params_list):
    """Stack a list of per-stage parameter pytrees into one pytree whose leaves carry
    a leading stages axis. All stages must share a structure (uniform stages — the
    usual pipeline shape)."""
    if not stage_params_list:
        raise ValueError('need at least one stage')
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *stage_params_list)


def unstack_stage_params(stacked, stage):
    """The inverse view: stage ``i``'s parameter pytree from the stacked tree."""
    return jax.tree.map(lambda leaf: leaf[stage], stacked)


def stage_partition_specs(stacked, stage_axis='stage'):
    """PartitionSpecs sharding every leaf's leading (stages) axis over
    ``stage_axis``; pair with ``NamedSharding`` to place stacked params."""
    return jax.tree.map(lambda leaf: P(stage_axis, *([None] * (leaf.ndim - 1))),
                        stacked)


def make_pipeline(stage_fn, mesh, stage_axis='stage', xs_spec=P(), out_spec=P(),
                  params_spec=None):
    """Build ``fn(stacked_params, xs) -> ys`` running ``stage_fn`` as a pipeline.

    :param stage_fn: ``(stage_params, microbatch) -> microbatch`` — one stage's
        computation; must preserve shape and dtype. It runs inside ``shard_map``, so
        it may use collectives over the mesh's OTHER axes (e.g.
        ``ops.sharded_moe.expert_alltoall_ffn`` over an ``'expert'`` axis — pipeline
        and expert parallelism in one program).
    :param mesh: mesh containing ``stage_axis``; other axes pass through (shard
        ``xs``'s non-microbatch dims over them via ``xs_spec``).
    :param xs_spec: PartitionSpec of ``xs`` (``[n_micro, ...microbatch...]``); dim 0
        is the microbatch stream and must NOT be sharded over ``stage_axis``.
    :param out_spec: PartitionSpec of the output (same layout as ``xs``).
    :param params_spec: in_spec (pytree prefix) for the stacked params; default
        ``P(stage_axis)`` shards only the leading stages axis and replicates the
        rest. Pass per-leaf specs like ``P('stage', 'expert', None, None)`` to ALSO
        shard stage weights over other mesh axes; every leaf's dim 0 must still be
        sharded over ``stage_axis`` (each device holds exactly its stage's slice).
    :returns: a function usable under ``jit``: feeds microbatch ``m`` to stage 0 at
        tick ``m``, collects stage ``n-1`` outputs, returns them replicated over the
        stage axis (other axes per ``out_spec``).
    """
    if stage_axis not in mesh.shape:
        raise ValueError('mesh has no axis {!r} (axes: {})'
                         .format(stage_axis, dict(mesh.shape)))
    if params_spec is None:
        params_spec = P(stage_axis)
    # None-preserving traversal: a None leaf is the conventional 'replicated'
    # spelling and MUST be rejected too — shard_map would replicate the stacked
    # params over the stage axis and leaf[0] would silently serve stage 0's
    # weights on every stage.
    specs = jax.tree.leaves(params_spec,
                            is_leaf=lambda leaf: leaf is None or isinstance(leaf, P))
    for spec in specs:
        if spec is None or not spec or spec[0] != stage_axis:
            raise ValueError('params_spec leaf {} must shard dim 0 over {!r} '
                             '(each device holds its own stage)'
                             .format(spec, stage_axis))
    n_stages = mesh.shape[stage_axis]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local_fn(stacked_local, xs):
        # P(stage_axis) shards each leaf's leading dim to length 1: this stage's params.
        params = jax.tree.map(lambda leaf: leaf[0], stacked_local)
        idx = lax.axis_index(stage_axis)
        n_micro = xs.shape[0]
        state = jnp.zeros(xs.shape[1:], xs.dtype)
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outputs = carry
            feed = lax.dynamic_index_in_dim(xs, jnp.minimum(t, n_micro - 1), 0,
                                            keepdims=False)
            inp = jnp.where(idx == 0, feed, state)
            out = stage_fn(params, inp)
            if out.shape != inp.shape or out.dtype != inp.dtype:
                raise ValueError(
                    'pipeline stage_fn must preserve shape/dtype: {} {} -> {} {}'
                    .format(inp.shape, inp.dtype, out.shape, out.dtype))
            done = t - (n_stages - 1)
            updated = lax.dynamic_update_index_in_dim(outputs, out,
                                                      jnp.maximum(done, 0), 0)
            is_last = idx == n_stages - 1
            outputs = jnp.where(jnp.logical_and(is_last, done >= 0), updated, outputs)
            state = lax.ppermute(out, stage_axis, perm)
            return (state, outputs), None

        steps = n_micro + n_stages - 1
        (_, outputs), _ = lax.scan(tick, (state, outputs), jnp.arange(steps))
        # The buffer is authoritative only on the last stage; the masked psum makes
        # every stage agree so the result is truly replicated over the stage axis.
        is_last = lax.axis_index(stage_axis) == n_stages - 1
        return lax.psum(jnp.where(is_last, outputs, jnp.zeros_like(outputs)),
                        stage_axis)

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(params_spec, xs_spec),
                         out_specs=out_spec, check_vma=False)


def microbatch(batch, n_micro):
    """Split ``[batch, ...]`` into ``[n_micro, batch/n_micro, ...]`` (the pipeline's
    input layout). Batch must divide evenly — pad upstream (the loaders' pad-and-mask
    path) rather than here."""
    leading = batch.shape[0]
    if leading % n_micro != 0:
        raise ValueError('batch {} not divisible into {} microbatches'
                         .format(leading, n_micro))
    return batch.reshape((n_micro, leading // n_micro) + batch.shape[1:])
