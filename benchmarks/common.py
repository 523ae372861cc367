"""Pieces that the configurations, the harness and the reference share: seeds,
weights drawn from the seed, per-leaf norms, the comparison numbers and the
low-precision rounding of the control."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def sub_seeds(seed):
    """Independent 32-bit seeds for the read order, the crops, the weights and the
    window's sampled batches, from any whole ``--seed`` (wider than 32 bits too)."""
    reader, crop, weights, sample = np.random.SeedSequence(int(seed)).generate_state(4)
    return {'reader': int(reader), 'crop': int(crop), 'weights': int(weights),
            'sample': int(sample)}


def leaf_name(path):
    """'a/b/c' for a key path of nested dicts."""
    return '/'.join(str(getattr(k, 'key', k)) for k in path)


def named_leaves(tree):
    return {leaf_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def weight_key(weight_seed):
    """The seed's key data for :func:`draw_weights`, passed to the jitted call as an
    argument so that one compiled program serves every seed."""
    return np.random.SeedSequence(weight_seed).generate_state(2).astype(np.uint32)


def draw_weights(key_data, shapes, rule):
    """A tree like ``shapes`` (of ShapeDtypeStruct) whose leaf at name ``n`` is
    ``rule(n, shape)`` applied: ``('normal', std)`` or ``('const', value)``. Leaves
    depend on the key (:func:`weight_key`) and their name only. Call inside
    ``jax.jit``."""
    key = jax.random.wrap_key_data(key_data)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for path, leaf in flat:
        name = leaf_name(path)
        kind, value = rule(name, leaf.shape)
        if kind == 'normal':
            sub = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = value * jax.random.normal(sub, leaf.shape, jnp.float32)
        elif kind == 'const':
            x = jnp.full(leaf.shape, value, jnp.float32)
        else:
            raise ValueError('unknown weight rule {!r} for {}'.format(kind, name))
        leaves.append(x.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


def place(params, batches, shardings):
    """``params`` and each batch put on ``shardings`` (params', batches'), or left
    where they are when it is None."""
    if shardings is None:
        return params, batches
    return (jax.device_put(params, shardings[0]),
            [jax.device_put(b, shardings[1]) for b in batches])


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel()), tree)


@jax.jit
def change_norms(after, before):
    return jax.tree.map(
        lambda a, b: jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()),
        after, before)


def host_norms(norms):
    return {k: float(v) for k, v in named_leaves(jax.device_get(norms)).items()}


def leaf_gaps(got, want, skip_below=None):
    """Each leaf's gap between two ``{leaf: norm}``: ``|got - want|`` over the larger
    of the reference leaf's norm and the median leaf's. With ``skip_below``, leaves
    whose reference gradient norm is under that share of the median leaf's are left
    out (``skip_below`` is then ``(share, {leaf: reference gradient norm})``)."""
    if set(got) != set(want):
        raise ValueError('leaf sets differ: {}'.format(sorted(set(got) ^ set(want))[:5]))
    names = sorted(want)
    if skip_below is not None:
        share, grads = skip_below
        floor = share * float(np.median([grads[n] for n in names]))
        names = [n for n in names if grads[n] >= floor]
    median = float(np.median([want[n] for n in names]))
    return {n: abs(got[n] - want[n]) / max(want[n], median, 1e-30) for n in names}


# ------------------------------------------------------- the control's rounding

def _fp8(x):
    """Per-tensor scaled float8 e4m3 rounding, returned in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    # e4m3fn has no infinity: a quotient a rounding above 448 would become NaN
    scaled = jnp.clip(x / scale, -448.0, 448.0)
    return scaled.astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8_round(x):
    """Rounds ``x`` to fp8 going forward and its cotangent to fp8 going back."""
    return _fp8(x)


fp8_round.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def contract(fn, a, b, low):
    """``fn(a, b)`` at float32 ``highest``, or, with ``low``, as a program that
    computes in fp8 would: operands, result and every cotangent of a matmul or
    convolution rounded to fp8 (float32 accumulation inside)."""
    if low:
        return fp8_round(fn(fp8_round(a), fp8_round(b)))
    return fn(a, b)
