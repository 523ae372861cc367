"""The LM cell's train step at full width, compiled for a described TPU v5e with
no chip attached: what the chip's compiler would refuse, and the memory it
reckons. The topology is described inside a fixture, never at import: only one
process may load the TPU library."""
import pytest

from benchmarks import common, harness


@pytest.fixture(scope='module')
def one_chip():
    import os
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to test
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
    return SingleDeviceSharding(topo.devices[0])


def test_lm_step_fits_one_v5e(one_chip):
    import jax
    import jax.numpy as jnp
    jax.config.update('jax_enable_compilation_cache', False)
    cell = harness.Cell(harness.load_spec(), 'cgpt1p3b.tokens_stream')
    program = cell.module.Program(cell.cfg)
    put = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(program.init, common.weight_key(1))
    batch = {'id': jax.ShapeDtypeStruct((cell.global_batch,), jnp.int32),
             'tokens': jax.ShapeDtypeStruct((cell.global_batch, cell.cfg['seq_len']),
                                            jnp.int32)}
    compiled = jax.jit(program.step, donate_argnums=0).lower(put(state), put(batch)).compile()
    memory = compiled.memory_analysis()
    print(memory)
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15 * 2 ** 30
