"""The served path's device programs compile for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a
described v5e and refuses what the chip would refuse (misaligned
blocks, too much fast memory, programs that do not fit). Nothing runs,
so these tests say nothing about results or speed. The topology is
described inside a fixture, never at import, so every test worker
collects the same tests and only the one given this file loads the TPU
library."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.icu_lstm import ICU_WORKLOADS
from repro.core import scheduler_jax
from repro.kernels import ops
from repro.kernels.lstm_cell import lstm_cell
from repro.models.lstm import ICULSTM


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to test here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batch", [8, 16])
@pytest.mark.parametrize("cfg", ICU_WORKLOADS, ids=lambda c: c.name)
def test_lstm_cell_compiles_for_v5e(one_chip, cfg, batch):
    f32 = jnp.float32
    args = (_spec((batch, cfg.input_dim), f32, one_chip),
            _spec((batch, cfg.hidden), f32, one_chip),
            _spec((batch, cfg.hidden), f32, one_chip),
            _spec((cfg.input_dim, 4, cfg.hidden), f32, one_chip),
            _spec((cfg.hidden, 4, cfg.hidden), f32, one_chip),
            _spec((4, cfg.hidden), f32, one_chip))
    compiled = jax.jit(lambda *a: lstm_cell(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cfg", ICU_WORKLOADS, ids=lambda c: c.name)
def test_icu_forward_compiles_for_v5e(one_chip, cfg, monkeypatch):
    # the platform check in ops picks the reference on this CPU; steer
    # the model onto the kernel the chip would run
    monkeypatch.setattr(ops, "lstm_step", lambda *a: lstm_cell(
        *a, interpret=False))
    model = ICULSTM(cfg)
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          model.param_specs())
    x = _spec((8, cfg.seq_len, cfg.input_dim), jnp.float32, one_chip)
    compiled = jax.jit(model.forward).lower(params, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode,batch,rows,slots", [
    ("round", 32, 112, 112),     # 32 wards x 100 jobs, independent plans
    ("pass", 32, 128, 16),       # a sweep against interval reservations
])
def test_tabu_search_compiles_for_v5e(one_chip, mode, batch, rows, slots):
    f32, i32 = jnp.float32, jnp.int32
    n_tiers = scheduler_jax.N_MACHINES
    args = (_spec((batch, rows), i32, one_chip),             # assign0
            _spec((batch, rows), f32, one_chip),             # rel
            _spec((batch, rows), f32, one_chip),             # w
            _spec((batch, rows, n_tiers), f32, one_chip),    # proc
            _spec((batch, rows, n_tiers), f32, one_chip),    # trans
            _spec((batch, rows), jnp.bool_, one_chip),       # movable
            _spec((batch, slots), i32, one_chip),            # mov_idx
            _spec((batch, slots), jnp.bool_, one_chip),      # mov_ok
            _spec((), i32, one_chip),                        # max_rounds
            _spec((batch, 1), f32, one_chip),                # busy_c
            _spec((batch, 1), f32, one_chip))                # busy_e
    compiled = scheduler_jax._tabu_run_batched.lower(
        *args, objective="weighted", mode=mode).compile()
    assert compiled.memory_analysis() is not None
    # the batched search picks this regime at this shape
    assert scheduler_jax.kernel_regime(slots, rows) == mode
    assert "while" in compiled.as_text()


@pytest.mark.parametrize("mode,batch,rows,slots", [
    ("round", 32, 112, 112),
    ("pass", 32, 128, 16),
])
def test_packed_tabu_search_compiles_for_v5e(one_chip, mode, batch, rows,
                                             slots):
    # the served entry: one int32 buffer in, one (B, rows + 2) array out
    layout = (batch, rows, slots, 1, 1)
    _, size = scheduler_jax._packed_fields(layout)
    buf = _spec((size,), jnp.int32, one_chip)
    lowered = scheduler_jax._tabu_run_packed.lower(
        buf, layout, "weighted", mode=mode)
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    (out,) = jax.tree.leaves(lowered.out_info)
    assert out.shape == (batch, rows + 2) and out.dtype == jnp.int32
    assert scheduler_jax.kernel_regime(slots, rows) == mode
    assert "while" in compiled.as_text()


@pytest.mark.parametrize("mode,rows", [("round", 32), ("pass", 48)])
def test_pooled_cloud_searches_compile_for_v5e(one_chip, mode, rows):
    # metro15icu.replan's searches: one ward's jobs and the other wards'
    # reservations in 32 or 48 rows, 16 movable slots, 15 cloud machines
    layout = (1, rows, 16, 15, 1)
    _, size = scheduler_jax._packed_fields(layout)
    buf = _spec((size,), jnp.int32, one_chip)
    compiled = scheduler_jax._tabu_run_packed.lower(
        buf, layout, "weighted", mode=mode).compile()
    assert scheduler_jax.kernel_regime(16, rows) == mode
    assert "while" in compiled.as_text()
