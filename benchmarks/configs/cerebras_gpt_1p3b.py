"""Cerebras-GPT 1.3B's layers trained by Adam: the program's step, and its plain
reference in ``jax.numpy`` at float32 ``highest``.

The program is ``petastorm_tpu.models.TransformerLM`` at this model's widths, in
bfloat16 with float32 logits, its attention on the Pallas flash kernels. The
reference computes the same equations (pre-LN blocks, tanh GELU, learned
positions, untied head with a bias, no bias on the attention projections) with
dense causal attention, each block rematerialised so that it fits beside
Adam's state; it imports nothing of the program.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmarks import common, flops

WORK_UNIT = 'tokens'
HIGHEST = jax.lax.Precision.HIGHEST


def weight_rule(name, shape):
    leaf = name.rsplit('/', 1)[-1]
    if leaf in ('kernel', 'embedding'):
        return ('normal', 0.02)
    if leaf == 'scale':
        return ('const', 1.0)
    if leaf == 'bias':
        return ('const', 0.0)
    raise ValueError('no weight rule for ' + name)


def _adam(cfg):
    opt = cfg['optimizer']
    return optax.adam(opt['learning_rate'], b1=opt['b1'], b2=opt['b2'], eps=opt['eps'])


class Program(object):
    """The system under test: TransformerLM's train step on the loader's batches."""

    def __init__(self, cfg, mesh=None):
        from petastorm_tpu.models import TransformerLM, next_token_loss
        from petastorm_tpu.ops.flash_attention import flash_attention
        if cfg['n_inner'] != 4 * cfg['n_embd'] or cfg['n_embd'] != cfg['n_head'] * cfg[
                'head_dim']:
            raise ValueError('TransformerLM has an MLP of 4 x n_embd and n_embd = heads x '
                             'head_dim')
        self.cfg = cfg
        self.model = TransformerLM(
            vocab=cfg['vocab_size'], embed=cfg['n_embd'], heads=cfg['n_head'],
            layers=cfg['n_layer'], max_len=cfg['n_positions'],
            dtype=jnp.dtype(cfg['compute_dtype']),
            attention_fn=lambda q, k, v: flash_attention(q, k, v, causal=True))
        self.tx = _adam(cfg)
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0),
                                     jnp.zeros((1, cfg['seq_len']), jnp.int32))
        model, tx = self.model, self.tx

        def step(state, batch):
            params, opt_state = state
            tokens = batch['tokens']
            loss, grads = jax.value_and_grad(
                lambda p: next_token_loss(model.apply(p, tokens), tokens))(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        self.step = step

    def init(self, key):
        params = common.draw_weights(key, self.shapes, weight_rule)
        return params, self.tx.init(params)

    @staticmethod
    def params(state):
        return state[0]['params']

    def first_grads(self, state):
        """The gradient of the first step, from Adam's first moment then:
        ``mu = (1 - b1) g``."""
        b1 = self.cfg['optimizer']['b1']
        return jax.tree.map(lambda m: m / (1.0 - b1), state[1][0].mu['params'])

    def init_params(self, key):
        return common.draw_weights(key, self.shapes, weight_rule)['params']

    def flops_per_chip_step(self, compiled, chips):
        """Model operations of one step on one chip, by the analytic count:
        recomputed work and the masked half of causal attention do not count."""
        cfg = self.cfg
        return flops.transformer_train_flops(
            cfg['batch_per_chip'], cfg['seq_len'], cfg['vocab_size'], cfg['n_embd'],
            cfg['n_layer'], cfg['n_inner'])

    def work(self, batch_rows):
        return batch_rows * self.cfg['seq_len']

    @staticmethod
    def attention():
        """What the flash kernels' roofline needs to know beyond the trace."""
        return {'causal': True}


# ------------------------------------------------------------------ reference

def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p['scale'] + p['bias']


def _block(cfg, low, x, p):
    dot = functools.partial(common.contract,
                            functools.partial(jnp.matmul, precision=HIGHEST), low=low)
    b, t, e = x.shape
    heads = cfg['n_head']
    eps = cfg['layer_norm_epsilon']
    h = _layer_norm(x, p['LayerNorm_0'], eps)
    q, k, v = jnp.split(dot(h, p['Dense_0']['kernel']), 3, axis=-1)
    q, k, v = (y.reshape(b, t, heads, e // heads).transpose(0, 2, 1, 3) for y in (q, k, v))
    s = dot(q, k.transpose(0, 1, 3, 2)) * (e // heads) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    attn = dot(jax.nn.softmax(s, axis=-1), v).transpose(0, 2, 1, 3).reshape(b, t, e)
    x = x + dot(attn, p['Dense_1']['kernel'])
    h = _layer_norm(x, p['LayerNorm_1'], eps)
    h = jax.nn.gelu(dot(h, p['Dense_2']['kernel']) + p['Dense_2']['bias'], approximate=True)
    return x + dot(h, p['Dense_3']['kernel']) + p['Dense_3']['bias']


def reference_loss(cfg, params, batch, low=False, rows=None):
    tokens = batch['tokens'] if rows is None else batch['tokens'][:rows]
    t = tokens.shape[1]
    lookup = common.fp8_round if low else (lambda table: table)
    x = lookup(params['Embed_0']['embedding'])[tokens] + lookup(
        params['Embed_1']['embedding'])[:t][None]
    block = jax.checkpoint(functools.partial(_block, cfg, low))
    for i in range(cfg['n_layer']):
        x = block(x, params['Block_{}'.format(i)])
    x = _layer_norm(x, params['LayerNorm_0'], cfg['layer_norm_epsilon'])
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    logits = common.contract(dot, x, params['Dense_0']['kernel'], low) + params['Dense_0'][
        'bias']
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def _param_shapes(cfg):
    e, f, v = cfg['n_embd'], cfg['n_inner'], cfg['vocab_size']
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    norm = lambda: {'scale': s(e), 'bias': s(e)}  # noqa: E731
    tree = {'Embed_0': {'embedding': s(v, e)}, 'Embed_1': {'embedding': s(cfg['n_positions'], e)},
            'LayerNorm_0': norm(), 'Dense_0': {'kernel': s(e, v), 'bias': s(v)}}
    for i in range(cfg['n_layer']):
        tree['Block_{}'.format(i)] = {
            'LayerNorm_0': norm(), 'LayerNorm_1': norm(),
            'Dense_0': {'kernel': s(e, 3 * e)}, 'Dense_1': {'kernel': s(e, e)},
            'Dense_2': {'kernel': s(e, f), 'bias': s(f)},
            'Dense_3': {'kernel': s(f, e), 'bias': s(e)}}
    return {'params': tree}


def reference_run(cfg, weight_seed, batches, low=False, rows=None, shardings=None):
    """Three Adam steps of the reference from the seed's weights over ``batches``
    (host dicts of ``tokens``): the losses, the first gradient's norms and the
    parameters' change norms after the last step, per leaf."""
    shapes = _param_shapes(cfg)
    params, batches = common.place(
        jax.jit(lambda k: common.draw_weights(k, shapes, weight_rule))(
            common.weight_key(weight_seed))['params'],
        batches, shardings)
    opt = cfg['optimizer']
    lr, b1, b2, eps = opt['learning_rate'], opt['b1'], opt['b2'], opt['eps']

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, count, batch):
        loss, grads = jax.value_and_grad(
            lambda p: reference_loss(cfg, p, batch, low, rows))(params)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree.map(lambda p, m, n: p - lr * (m / c1) / (jnp.sqrt(n / c2) + eps),
                              params, mu, nu)
        return params, mu, nu, loss, common.leaf_norms(grads)

    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grads = [], None
    for count, batch in enumerate(batches, 1):
        params, mu, nu, loss, norms = step(params, mu, nu, np.float32(count), batch)
        losses.append(float(loss))
        if grads is None:
            grads = common.host_norms(norms)
    del mu, nu
    start, _ = common.place(
        jax.jit(lambda k: common.draw_weights(k, shapes, weight_rule))(
            common.weight_key(weight_seed))['params'],
        [], shardings)
    return {'loss': losses, 'grad': grads,
            'change': common.host_norms(common.change_norms(params, start))}
