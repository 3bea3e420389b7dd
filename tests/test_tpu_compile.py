"""The Pallas kernels compile for a TPU v5e chip (described, not attached).

Interpret mode cannot see what Mosaic refuses: a gather it cannot lower,
or more scoped VMEM than the kernel may use.  These tests compile the
forward kernel and the forward+backward pair at published widths against
a described ``v5e:2x2`` topology with the TPU compiler installed beside
JAX, and check that the compiled program really holds the kernel.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.feature_map import TaylorConfig
from repro.kernels.taylor_attention import (
    taylor_attention_kernel,
    taylor_attention_kernel_trainable,
)

# (h, hk, d, n): qwen2-1.5b (GQA 12/2, d=128) and smollm-135m (GQA 9/3,
# d=64, padded to 128 lanes by the wrapper).
WIDTHS = {
    "qwen2-1.5b": (12, 2, 128, 2048),
    "smollm-135m": (9, 3, 64, 1024),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies  # noqa: PLC0415

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import (  # noqa: PLC0415
        compilation_cache,
    )

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(one_chip, widths):
    h, hk, d, n = widths
    q = jax.ShapeDtypeStruct((1, h, n, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, hk, n, d), jnp.bfloat16, sharding=one_chip)
    return q, kv, kv


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_forward_kernel_compiles_for_v5e(arch, one_chip, no_persistent_cache):
    compiled = taylor_attention_kernel.lower(
        *_shapes(one_chip, WIDTHS[arch])
    ).compile()
    assert _kernel_calls(compiled) == 1


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_forward_backward_kernels_compile_for_v5e(
    arch, one_chip, no_persistent_cache
):
    def loss(q, k, v):
        out = taylor_attention_kernel_trainable(
            q, k, v, TaylorConfig(), backward="pallas"
        )
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_shapes(one_chip, WIDTHS[arch])
    ).compile()
    # forward + the dq and dk/dv passes of the backward
    assert _kernel_calls(compiled) == 3
    # each backward pass carries its own name, and not the forward's
    names = [line.split("=")[0] for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("taylor_attention_kernel", "taylor_bwd_dq", "taylor_bwd_dkv"):
        assert sum(kernel in n for n in names) == 1, (kernel, names)


def test_kernels_compile_per_shard_on_a_data_mesh(
    topo, no_persistent_cache, monkeypatch
):
    """The partitioner cannot split a Mosaic kernel: on a mesh the taylor
    backend runs it under ``shard_map`` (batch over "dp", heads over
    "tp"), and the sharded forward+backward compiles for four chips."""
    import numpy as np  # noqa: PLC0415
    from jax.sharding import AxisType, Mesh, NamedSharding  # noqa: PLC0415
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    from repro.backends import get_backend  # noqa: PLC0415
    from repro.configs import get_config  # noqa: PLC0415
    from repro.distributed import api as dist  # noqa: PLC0415

    # the compile targets the described chips, but this process runs on
    # the CPU: steer the backend's platform check to the TPU branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("smollm-135m")
    backend = get_backend(cfg.attention)
    assert backend.resolve_impl(cfg) == "pallas"
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    h, hk, d, n = WIDTHS["smollm-135m"]
    batch = NamedSharding(mesh, P("data"))
    q = jax.ShapeDtypeStruct((8, h, n, d), jnp.bfloat16, sharding=batch)
    kv = jax.ShapeDtypeStruct((8, hk, n, d), jnp.bfloat16, sharding=batch)

    def loss(q, k, v):
        out = backend.apply(q, k, v, cfg)
        return jnp.sum(out.astype(jnp.float32))

    with dist.sharding_rules(mesh, dist.rules_for_mesh(mesh)):
        lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    assert _kernel_calls(lowered.compile()) == 3


def test_decode_scan_updates_the_moment_stack_in_place(
    one_chip, no_persistent_cache
):
    """The serve engine's 16-step decode dispatch at qwen2-1.5b widths
    (28 layers, 6 slots) updates the stacked moment state in place: no
    copy of the whole stack and no fresh buffer for it, per step or per
    dispatch, and next to no temporaries beside the donated state."""
    from repro.configs import get_config  # noqa: PLC0415
    from repro.models.lm import lm_init  # noqa: PLC0415
    from repro.serve.engine import _jitted_decode_scan  # noqa: PLC0415
    from repro.serve.slots import init_slot_caches  # noqa: PLC0415

    cfg = get_config("qwen2-1.5b")
    slots, n_max = 6, 9216

    def on_chip(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: on_chip(x, jnp.bfloat16),
        jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg)),
    )
    caches = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_slot_caches(cfg, slots, n_max, jnp.dtype(cfg.dtype))))

    def per_slot(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=one_chip)

    compiled = _jitted_decode_scan(cfg, 16, False, 0).lower(
        params, caches, per_slot(jnp.int32), per_slot(jnp.int32),
        per_slot(jnp.bool_), per_slot(jnp.float32), per_slot(jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
    ).compile()

    # the stacked second moment, [n_groups, run, slots, hk, d, d, d] f32
    s2 = max(jax.tree.leaves(caches["group"]), key=lambda x: x.size)
    stack = f"f32[{','.join(map(str, s2.shape))}]"
    assert stack == "f32[28,1,6,2,128,128,128]"
    text = compiled.as_text()
    assert stack in text
    whole_stack = [
        line.strip()[:160] for line in text.splitlines()
        if f"= {stack}" in line
        and (" copy(" in line or 'custom_call_target="AllocateBuffer"' in line)
    ]
    assert not whole_stack, whole_stack
    temp_gib = compiled.memory_analysis().temp_size_in_bytes / 2**30
    assert temp_gib < 0.5, temp_gib


def test_decode_scan_runs_the_decode_kernel_on_the_moment_stack(
    one_chip, no_persistent_cache, monkeypatch
):
    """On the chip (auto impl resolves to "pallas") the same 16-step
    decode dispatch hands each layer's second moment to the Pallas decode
    kernel: the kernel is the only operation that touches a whole layer's
    ``S2`` slice (``[6, 2, 128, 128, 128]`` in any dtype), the stack is
    updated in place with no copy, and the temporaries stay small."""
    import re  # noqa: PLC0415

    from repro.configs import get_config  # noqa: PLC0415
    from repro.models.lm import lm_decode_kernel_layers, lm_init  # noqa: PLC0415
    from repro.serve.engine import _decode_scan_fn  # noqa: PLC0415
    from repro.serve.slots import init_slot_caches  # noqa: PLC0415

    # compiled for the described chip from this CPU process: steer the
    # backend's platform check to the TPU branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("qwen2-1.5b")
    assert lm_decode_kernel_layers(cfg) == cfg.n_layers == 28
    slots, n_max = 6, 9216

    def on_chip(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=one_chip)

    def per_slot(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: on_chip(x, jnp.bfloat16),
        jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg)),
    )
    caches = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_slot_caches(cfg, slots, n_max, jnp.dtype(cfg.dtype))))
    # a jit of its own: the engine's cached one may hold a CPU-branch trace
    scan = jax.jit(_decode_scan_fn(cfg, 16, False, 0), donate_argnums=(1,))
    compiled = scan.lower(
        params, caches, per_slot(jnp.int32), per_slot(jnp.int32),
        per_slot(jnp.bool_), per_slot(jnp.float32), per_slot(jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
    ).compile()

    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 and "taylor_decode_s2" in kernels[0], kernels
    stack = "f32[28,1,6,2,128,128,128]"
    assert stack in kernels[0]
    layer_slice = re.compile(r"[a-z0-9]+\[(?:1,)*6,2,128,128,128\]")
    touching = [line.strip()[:160] for line in text.splitlines()
                if layer_slice.search(line)]
    assert not touching, touching
    whole_stack = [
        line.strip()[:160] for line in text.splitlines()
        if f"= {stack}" in line
        and (" copy(" in line or 'custom_call_target="AllocateBuffer"' in line)
    ]
    assert not whole_stack, whole_stack
    temp_gib = compiled.memory_analysis().temp_size_in_bytes / 2**30
    assert temp_gib < 0.5, temp_gib
