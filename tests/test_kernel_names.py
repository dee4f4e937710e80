"""Every Pallas kernel carries its own name into the compiled program.

Lowered for ``platforms=["tpu"]`` through ``jax.export`` (Mosaic
serializes the kernel at lowering, so neither a chip nor libtpu is
needed), each kernel's ``tpu_custom_call`` has the ``name=`` of its
``pl.pallas_call`` as the component before ``pallas_call`` in its
location. XLA names the HLO instruction after that component (compiled
for a described v5e: ``%flash_fwd.1 = ... custom-call``; without the
name it was ``%checkpoint.3`` or ``%shard_map.2``, whatever wrapped the
call), and ``perfbench/xplane.py`` names a device operation by its
instruction: a kernel metric finds its kernel after a refactor.
"""

import importlib
import re

import pytest

import jax
import jax.numpy as jnp
from jax import export

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
ln = importlib.import_module("paddle_tpu.ops.pallas.layer_norm")
pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
ss = importlib.import_module("paddle_tpu.ops.pallas.selective_scan")


def _flash_loss(q, k, v):
    return fa.flash_attention(q, k, v, causal=True).astype(
        jnp.float32).sum()


def _ln_loss(x, g, b):
    return ln.fused_layer_norm(x, g, b).astype(jnp.float32).sum()


def _paged(q, k, v, tables, pos):
    return pa.paged_attention(q, k, v, tables, pos, interpret=False)


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _scan(x, dt, a, b, c, d, z, last):
    return ss.selective_scan(x, dt, a, b, c, d, z, last, interpret=False)


_QKV = (_s((2, 4, 256, 128), jnp.bfloat16),) * 3
_LN = (_s((256, 512)), _s((512,)), _s((512,)))
_PAGED = (_s((4, 4, 1, 128)), _s((32, 4, 16, 128)), _s((32, 4, 16, 128)),
          _s((4, 8), jnp.int32), _s((4,), jnp.int32))
_SCAN = (_s((2, 256, 1024)), _s((2, 256, 1024)), _s((16, 1024)),
         _s((2, 256, 16)), _s((2, 256, 16)), _s((1024,)),
         _s((2, 256, 1024)), _s((2,), jnp.int32))
#: kernel name -> (function, argument shapes); a backward program holds
#: its forward kernel too
CASES = {
    "flash_fwd": (_flash_loss, _QKV),
    "flash_bwd_dq": (jax.grad(_flash_loss, argnums=(0, 1, 2)), _QKV),
    "flash_bwd_dkv": (jax.grad(_flash_loss, argnums=(0, 1, 2)), _QKV),
    "layer_norm_fwd": (_ln_loss, _LN),
    "layer_norm_bwd": (jax.grad(_ln_loss, argnums=(0, 1, 2)), _LN),
    "paged_decode_attn": (_paged, _PAGED),
    "selective_scan": (_scan, _SCAN),
}


def kernel_locations(fn, shapes, module=False):
    """The location of every ``tpu_custom_call`` of ``fn`` lowered for
    the TPU, e.g. ``jit(f)/checkpoint/flash_fwd/pallas_call``
    (``module``: and the module's text)."""
    # as the chip runs them: without the suite's x64, under which the
    # literal zeros of the BlockSpec index maps lower as i64 and Mosaic
    # refuses them
    with jax.enable_x64(False):
        text = export.export(jax.jit(fn), platforms=["tpu"])(
            *shapes).mlir_module()
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text,
                           flags=re.M))
    out = []
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
            out.append(locs[ref.group(1)] if ref else line)
    return (out, text) if module else out


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_carries_its_name_to_the_custom_call(monkeypatch, kernel):
    for mod in (fa, ln, pa):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    fn, shapes = CASES[kernel]
    locations = kernel_locations(fn, shapes)
    assert locations, "no tpu_custom_call was lowered"
    # every kernel of the program is named, and this one is among them
    names = [re.sub(r"^.*\((\w+)\)+$", r"\1", loc.split("/")[-2])
             for loc in locations]
    assert all(loc.endswith("/pallas_call") for loc in locations)
    assert set(names) <= set(CASES), locations
    assert kernel in names, locations


def test_wrappers_do_not_rename_a_kernel():
    """What PR 23's ledger showed: under ``jax.checkpoint`` the flash
    kernel was ``checkpoint_...`` and under ``shard_map``
    ``shard_map_...``. The component before ``pallas_call`` is now the
    kernel's own name whatever wraps it; and since PR 60 the kernels are
    traced once a shape under a ``jax.jit`` of their own, so the wrapper is
    in the module (``checkpoint``) and a kernel's location is the one it
    was first traced at."""
    def wrapped(q, k, v):
        return jax.checkpoint(_flash_loss)(q, k, v)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_interpret", lambda: False)
        plain, plain_module = kernel_locations(
                jax.grad(_flash_loss, argnums=(0, 1, 2)), _QKV, module=True)
        remat, remat_module = kernel_locations(
                jax.grad(wrapped, argnums=(0, 1, 2)), _QKV, module=True)
    assert "checkpoint" in remat_module
    assert "checkpoint" not in plain_module
    for locations in (plain, remat):
        kernels = sorted(re.sub(r"^.*\((\w+)\)+$", r"\1",
                                loc.split("/")[-2]) for loc in locations)
        assert [k for k in kernels if k.startswith("flash_bwd")] == \
            ["flash_bwd_dkv", "flash_bwd_dq"]
        assert "flash_fwd" in kernels
