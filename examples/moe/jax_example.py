"""Scaling-families demo: expert-parallel MoE and pipeline-parallel training fed from
a petastorm_tpu store.

The reference's examples only scale data-parallel (torch DistributedSampler / Horovod
shard-by-rank); this example shows the two TPU-native families beyond dp, both fed by
the SAME input pipeline (``write_rows`` → ``make_reader`` → ``JaxDataLoader``):

- **default (ep)**: :class:`petastorm_tpu.models.MoETransformerLM` on a
  ``(data, expert)`` mesh — Switch-routed expert MLPs, expert weights placed by
  ``expert_partition_specs`` (leading experts axis over the ``'expert'`` mesh axis),
  the token all-to-all inserted by XLA from the sharding annotations.
- **``--pipeline-stages N`` (pp)**: dense transformer blocks pipelined over a
  ``('stage', 'data')`` mesh via :func:`petastorm_tpu.parallel.make_pipeline` — the
  GPipe microbatch schedule as one jitted SPMD program, gradients through
  ``ppermute``.

Run: ``python -m examples.moe.jax_example``
     ``python -m examples.moe.jax_example --pipeline-stages 4``
"""

import argparse
import os
import tempfile

import numpy as np

VOCAB = 256
EMBED = 64
HEADS = 4


def build_dataset(url, num_docs=256, seq_len=128, seed=0):
    """Synthetic learnable corpus — delegates to the long_context example's builder
    (ONE definition of the repeating-bigram language; both examples share VOCAB=256)
    so the two examples cannot diverge."""
    from examples.long_context.jax_example import build_dataset as build_docs
    return build_docs(url, num_docs=num_docs, seq_len=seq_len, seed=seed)


def train_moe(dataset_url, batch_size=8, epochs=2, expert_axis_size=None,
              learning_rate=1e-2):
    """Expert-parallel training: one step per loader batch on a (data, expert) mesh."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from petastorm_tpu import make_reader
    from petastorm_tpu.models import (MoETransformerLM, expert_partition_specs,
                                      moe_aux_total, next_token_loss)
    from petastorm_tpu.parallel import JaxDataLoader, make_mesh

    n_dev = len(jax.devices())
    if expert_axis_size is None:
        expert_axis_size = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    if n_dev % expert_axis_size:
        raise ValueError('expert axis {} does not divide device count {}'
                         .format(expert_axis_size, n_dev))
    mesh = make_mesh(('data', 'expert'),
                     axis_sizes=(n_dev // expert_axis_size, expert_axis_size))
    model = MoETransformerLM(vocab=VOCAB, embed=EMBED, heads=HEADS, layers=2,
                             num_experts=max(2, expert_axis_size), moe_every=2,
                             dtype=jnp.float32, expert_axis='expert')
    optimizer = optax.adam(learning_rate)

    def loss_fn(params, tokens):
        logits, mods = model.apply(params, tokens, mutable='losses')
        return next_token_loss(logits, tokens) + moe_aux_total(mods, weight=0.01)

    @jax.jit
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    reader = make_reader(dataset_url, schema_fields=['tokens'], num_epochs=epochs,
                         shuffle_row_groups=True, seed=7)
    loss = params = opt_state = None
    with jax.set_mesh(mesh):
        with JaxDataLoader(reader, batch_size=batch_size, mesh=mesh,
                           partition_spec=P('data')) as loader:
            for step, batch in enumerate(loader):
                if params is None:
                    params = {'params': model.init(jax.random.PRNGKey(0),
                                                   batch['tokens'])['params']}
                    specs = expert_partition_specs(params)
                    params = jax.device_put(params, jax.tree.map(
                        lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda leaf: isinstance(leaf, P)))
                    opt_state = optimizer.init(params)
                params, opt_state, loss = train_step(params, opt_state,
                                                     batch['tokens'])
                if step % 20 == 0:
                    print('step {} loss {:.4f}'.format(step, float(loss)))
            print('input pipeline stats:', loader.stats.as_dict())
    return params, float(loss)


def train_pipeline(dataset_url, n_stages=4, batch_size=8, n_micro=2, epochs=2,
                   learning_rate=1e-2):
    """Pipeline-parallel training: embed → N pipelined Blocks → logits head, stage
    params sharded over 'stage', batch sharded over 'data', microbatches streamed
    through the GPipe schedule."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from petastorm_tpu import make_reader
    from petastorm_tpu.models.transformer import Block, dense_causal_attention
    from petastorm_tpu.parallel import (JaxDataLoader, make_mesh, make_pipeline,
                                        microbatch, stack_stage_params,
                                        stage_partition_specs)

    n_dev = len(jax.devices())
    if n_dev % n_stages:
        raise ValueError('stages {} do not divide device count {}'
                         .format(n_stages, n_dev))
    mesh = make_mesh(('stage', 'data'), axis_sizes=(n_stages, n_dev // n_stages))
    block = Block(heads=HEADS, attention_fn=dense_causal_attention,
                  dtype=jnp.float32)
    pipe = make_pipeline(lambda p, mb: block.apply({'params': p}, mb), mesh,
                         xs_spec=P(None, 'data', None, None),
                         out_spec=P(None, 'data', None, None))
    optimizer = optax.adam(learning_rate)

    def init_params(rng_key, seq_len):
        rng = np.random.RandomState(0)
        probe = jnp.zeros((2, seq_len, EMBED), jnp.float32)
        stacked = stack_stage_params(
            [block.init(jax.random.fold_in(rng_key, i), probe)['params']
             for i in range(n_stages)])
        stacked = jax.device_put(stacked, jax.tree.map(
            lambda s: NamedSharding(mesh, s), stage_partition_specs(stacked),
            is_leaf=lambda leaf: isinstance(leaf, P)))
        replicated = NamedSharding(mesh, P(None, None))
        extra = {
            'embed': jax.device_put(
                jnp.asarray(rng.randn(VOCAB, EMBED), jnp.float32) * 0.02, replicated),
            'w_out': jax.device_put(
                jnp.asarray(rng.randn(EMBED, VOCAB), jnp.float32) * 0.02, replicated),
        }
        return (stacked, extra)

    def loss_fn(params, tokens):
        stacked, extra = params
        xs = microbatch(extra['embed'][tokens], n_micro)   # [M, mb, T, E]
        logits = pipe(stacked, xs) @ extra['w_out']        # [M, mb, T, V]
        logp = jax.nn.log_softmax(logits[:, :, :-1], axis=-1)
        targets = microbatch(tokens, n_micro)[:, :, 1:]
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    @jax.jit
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    reader = make_reader(dataset_url, schema_fields=['tokens'], num_epochs=epochs,
                         shuffle_row_groups=True, seed=7)
    loss = params = opt_state = None
    with jax.set_mesh(mesh):
        with JaxDataLoader(reader, batch_size=batch_size, mesh=mesh,
                           partition_spec=P('data')) as loader:
            for step, batch in enumerate(loader):
                if params is None:
                    params = init_params(jax.random.PRNGKey(0),
                                         batch['tokens'].shape[1])
                    opt_state = optimizer.init(params)
                params, opt_state, loss = train_step(params, opt_state,
                                                     batch['tokens'])
                if step % 20 == 0:
                    print('step {} loss {:.4f}'.format(step, float(loss)))
            print('input pipeline stats:', loader.stats.as_dict())
    return params, float(loss)


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default=None)
    parser.add_argument('--num-docs', type=int, default=256)
    parser.add_argument('--seq-len', type=int, default=128)
    parser.add_argument('--batch-size', type=int, default=8)
    parser.add_argument('--epochs', type=int, default=2)
    parser.add_argument('--expert-axis', type=int, default=None,
                        help='expert mesh-axis size (default: 4 when the device '
                             'count divides, else 2, else 1)')
    parser.add_argument('--pipeline-stages', type=int, default=0,
                        help='train the pipeline-parallel configuration with this '
                             'many stages instead of the MoE one (0 = MoE)')
    parser.add_argument('--microbatches', type=int, default=2)
    args = parser.parse_args()

    url = args.dataset_url or os.path.join(
        tempfile.gettempdir(), 'moe_demo_{}x{}'.format(args.num_docs, args.seq_len))
    if not os.path.exists(os.path.join(url.replace('file://', ''),
                                       '_common_metadata')):
        print('materializing {} docs x {} tokens to {}'.format(
            args.num_docs, args.seq_len, url))
        build_dataset(url, args.num_docs, args.seq_len)
    if args.pipeline_stages:
        _, final_loss = train_pipeline(url, n_stages=args.pipeline_stages,
                                       batch_size=args.batch_size,
                                       n_micro=args.microbatches,
                                       epochs=args.epochs)
    else:
        _, final_loss = train_moe(url, batch_size=args.batch_size,
                                  epochs=args.epochs,
                                  expert_axis_size=args.expert_axis)
    print('final loss: {:.4f}'.format(final_loss))


if __name__ == '__main__':
    main()
