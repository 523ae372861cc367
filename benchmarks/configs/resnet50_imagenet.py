"""ResNet-50 trained by SGD with momentum: the program's step, and its plain
reference in ``jax.numpy`` at float32 ``highest``.

The program is ``petastorm_tpu.models.resnet.ResNet`` in bfloat16 with float32
batch norm. The reference below follows He et al. 2015 (v1.5: the stride sits on
the 3x3 convolution) with batch statistics over the whole batch, as the program
trains; it imports nothing of the program.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmarks import common

WORK_UNIT = 'rows'
HIGHEST = jax.lax.Precision.HIGHEST


def weight_rule(name, shape):
    leaf = name.rsplit('/', 1)[-1]
    if leaf == 'kernel':
        fan_in = int(np.prod(shape[:-1]))
        return ('normal', float(np.sqrt(2.0 / fan_in)) if len(shape) == 4
                else float(np.sqrt(1.0 / fan_in)))
    if leaf == 'scale' and name.endswith('BatchNorm_2/scale'):
        return ('const', 0.0)  # each block starts as identity (Goyal et al. 2017)
    if leaf in ('scale', 'var'):
        return ('const', 1.0)
    if leaf in ('bias', 'mean'):
        return ('const', 0.0)
    raise ValueError('no weight rule for ' + name)


def _sgd(cfg):
    opt = cfg['optimizer']
    return optax.sgd(opt['learning_rate'], momentum=opt['momentum'])


class Program(object):
    """The system under test: the Flax ResNet's train step on the loader's batches."""

    def __init__(self, cfg, mesh=None):
        from petastorm_tpu.models.resnet import ResNet
        from petastorm_tpu.ops.image import normalize_image
        self.cfg = cfg
        self.model = ResNet(stage_sizes=list(cfg['stage_sizes']),
                            num_filters=cfg['num_filters'],
                            num_classes=cfg['num_classes'],
                            dtype=jnp.dtype(cfg['compute_dtype']))
        self.tx = _sgd(cfg)
        hw = cfg['image_hw']
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0),
                                     jnp.zeros((1, hw, hw, 3), jnp.float32))
        norm = cfg['normalize']
        model, tx = self.model, self.tx

        def loss_fn(params, batch_stats, batch):
            x = normalize_image(batch['image'], norm['mean'], norm['std'],
                                dtype=jnp.dtype(cfg['compute_dtype']))
            logits, updates = model.apply({'params': params, 'batch_stats': batch_stats}, x,
                                          train=True, mutable=['batch_stats'])
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, batch['label'])
            return loss.mean(), updates['batch_stats']

        def step(state, batch):
            params, batch_stats, opt_state = state
            (loss, batch_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch_stats, batch)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), batch_stats, opt_state), loss

        self.step = step

    def init_params(self, key):
        return common.draw_weights(key, self.shapes, weight_rule)['params']

    def init(self, key):
        variables = common.draw_weights(key, self.shapes, weight_rule)
        params = variables['params']
        return params, variables['batch_stats'], self.tx.init(params)

    @staticmethod
    def params(state):
        return state[0]

    @staticmethod
    def attention():
        return None

    @staticmethod
    def first_grads(state):
        """The gradient of the first step, as SGD's momentum trace holds it then."""
        return state[2][0].trace

    @staticmethod
    def flops_per_chip_step(compiled, chips):
        """Operations of one step on one chip, by XLA's count of the compiled step
        (all of it is HLO: no custom kernel hides work from the count). For a
        program partitioned over a mesh the count is already one device's."""
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        return float(analysis['flops'])

    @staticmethod
    def work(batch_rows):
        return batch_rows


# ------------------------------------------------------------------ reference

def _conv(x, w, stride, padding, low):
    fn = functools.partial(jax.lax.conv_general_dilated, window_strides=(stride, stride),
                           padding=padding, dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
                           precision=HIGHEST)
    return common.contract(fn, x, w, low)


def _bn(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * p['scale'] + p['bias']


def reference_logits(cfg, params, images, low=False):
    """uint8 [B, H, W, 3] -> float32 logits [B, classes]."""
    norm = cfg['normalize']
    x = (images.astype(jnp.float32) / 255.0 - jnp.asarray(norm['mean'])) / jnp.asarray(
        norm['std'])
    x = _conv(x, params['conv_init']['kernel'], 2, [(3, 3), (3, 3)], low)
    x = jax.nn.relu(_bn(x, params['bn_init']))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), 'SAME')
    n = 0
    for stage, blocks in enumerate(cfg['stage_sizes']):
        for b in range(blocks):
            p = params['BottleneckBlock_{}'.format(n)]
            n += 1
            stride = 2 if stage > 0 and b == 0 else 1
            y = jax.nn.relu(_bn(_conv(x, p['Conv_0']['kernel'], 1, 'SAME', low),
                                p['BatchNorm_0']))
            y = jax.nn.relu(_bn(_conv(y, p['Conv_1']['kernel'], stride, 'SAME', low),
                                p['BatchNorm_1']))
            y = _bn(_conv(y, p['Conv_2']['kernel'], 1, 'SAME', low), p['BatchNorm_2'])
            if 'conv_proj' in p:
                x = _bn(_conv(x, p['conv_proj']['kernel'], stride, 'SAME', low),
                        p['norm_proj'])
            x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    dense = functools.partial(jnp.dot, precision=HIGHEST)
    return common.contract(dense, x, params['Dense_0']['kernel'], low) + params['Dense_0'][
        'bias']


def reference_loss(cfg, params, batch, low=False, rows=None):
    images, labels = batch['image'], batch['label']
    if rows is not None:
        images, labels = images[:rows], labels[:rows]
    logits = reference_logits(cfg, params, images, low)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def reference_run(cfg, weight_seed, batches, low=False, rows=None, shardings=None):
    """Three SGD-with-momentum steps of the reference from the seed's weights over
    ``batches`` (host dicts of ``image`` and ``label``). Returns the losses, the
    first step's gradient norms and the parameters' change norms after the last
    step, each per leaf. ``low`` computes it as the control does; ``rows`` keeps
    only the first rows of each batch. ``shardings`` (params', batches') spreads
    the work over a mesh as the program's step is."""
    shapes = _param_shapes(cfg)
    params, batches = common.place(
        jax.jit(lambda k: common.draw_weights(k, shapes, weight_rule))(
            common.weight_key(weight_seed))['params'],
        batches, shardings)
    opt = cfg['optimizer']
    lr, momentum = opt['learning_rate'], opt['momentum']

    @jax.jit
    def step(params, trace, batch):
        loss, grads = jax.value_and_grad(
            lambda p: reference_loss(cfg, p, batch, low, rows))(params)
        trace = jax.tree.map(lambda t, g: g + momentum * t, trace, grads)
        params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
        return params, trace, loss, common.leaf_norms(grads)

    start = params
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, grads = [], None
    for batch in batches:
        params, trace, loss, norms = step(params, trace, batch)
        losses.append(float(loss))
        if grads is None:
            grads = common.host_norms(norms)
    return {'loss': losses, 'grad': grads,
            'change': common.host_norms(common.change_norms(params, start))}


def _param_shapes(cfg):
    """The parameter tree's shapes, built from the configuration alone (the same
    names as the program's tree, which the weight rule is keyed by)."""
    f = cfg['num_filters']
    tree = {'params': {}, 'batch_stats': {}}

    def conv(name, k, cin, cout, scope):
        scope[name] = {'kernel': jax.ShapeDtypeStruct((k, k, cin, cout), jnp.float32)}

    def bn(name, c, scope, stats):
        scope[name] = {'scale': jax.ShapeDtypeStruct((c,), jnp.float32),
                       'bias': jax.ShapeDtypeStruct((c,), jnp.float32)}
        stats[name] = {'mean': jax.ShapeDtypeStruct((c,), jnp.float32),
                       'var': jax.ShapeDtypeStruct((c,), jnp.float32)}

    p, s = tree['params'], tree['batch_stats']
    conv('conv_init', 7, 3, f, p)
    bn('bn_init', f, p, s)
    cin, n = f, 0
    for stage, blocks in enumerate(cfg['stage_sizes']):
        width = f * 2 ** stage
        for b in range(blocks):
            name = 'BottleneckBlock_{}'.format(n)
            n += 1
            p[name], s[name] = {}, {}
            conv('Conv_0', 1, cin, width, p[name])
            bn('BatchNorm_0', width, p[name], s[name])
            conv('Conv_1', 3, width, width, p[name])
            bn('BatchNorm_1', width, p[name], s[name])
            conv('Conv_2', 1, width, width * 4, p[name])
            bn('BatchNorm_2', width * 4, p[name], s[name])
            if b == 0:
                conv('conv_proj', 1, cin, width * 4, p[name])
                bn('norm_proj', width * 4, p[name], s[name])
            cin = width * 4
    p['Dense_0'] = {'kernel': jax.ShapeDtypeStruct((cin, cfg['num_classes']), jnp.float32),
                    'bias': jax.ShapeDtypeStruct((cfg['num_classes'],), jnp.float32)}
    return tree
