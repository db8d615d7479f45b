"""The built-in execution backends, registered as peers.

Each ``run`` body is the corresponding branch that used to live inline
in ``core.linear.apply`` (dense, jnp msGeMM, fused Pallas msGeMM,
int4 dequant) — moved behind the registry so numerics are unchanged —
plus ``int4_pallas``, the blocked dequant+MXU Pallas kernel, and
``msgemm_mxu``, which contracts the stored msGeMM codes on the MXU.

``run`` takes optional ``epilogue``/``bias``/``residual`` kwargs:
``dispatch.execute`` only passes them when the backend's ``epilogue_ok``
predicate accepted the requested :class:`core.epilogue.Epilogue` (and
the plan allows fusion) — the Pallas kernels then execute the tail
inside their final VMEM writeback; every other backend never sees an
epilogue and ``execute`` applies it unfused after ``run``.

Priorities: on a TPU ``msgemm_mxu`` runs uniform-code msGeMM weights
and the LUT kernel ``msgemm_pallas`` the learned codebooks; everywhere
else both run only in interpret mode, below ``msgemm_jnp``.
``int4_jnp`` outranks ``int4_pallas`` (the jnp dequant path is what
`mode='int4_dequant'` always did).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import linear as _linear
from repro.core import lut, packing, scales
from repro.dispatch.registry import register_backend


def _dot_rows(x: jnp.ndarray, w: jnp.ndarray, precision=None) -> jnp.ndarray:
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=x.dtype, precision=precision)


def _residual_cols(residual, m: int):
    """Model-layout residual (..., m) -> the kernels' (m, B) columns."""
    if residual is None:
        return None
    return residual.reshape(-1, m).T


def _out_dtype(epilogue, x):
    return (jnp.dtype(epilogue.out_dtype)
            if epilogue is not None and epilogue.out_dtype else x.dtype)


def _pallas_epilogue_ok(epilogue) -> bool:
    """The Pallas kernels fuse the full epilogue envelope: any
    activation in core.epilogue.ACTIVATIONS, bias, residual, out cast."""
    return True


def run_dense(spec, plan, params, x, *, k, precision=None, epilogue=None,
              bias=None, residual=None):
    return _dot_rows(x, params["w"], precision=precision)


def run_int4_jnp(spec, plan, params, x, *, k, precision=None, epilogue=None,
                 bias=None, residual=None):
    m = params["scales"].shape[0]
    d = spec.resolve_d(k, m)
    codes = _linear._codes(params, spec, k, d)
    qt = scales.QuantizedTensor(
        codes=codes, scales=params["scales"], block=spec.scale_block,
        shape=(codes.shape[0], k), codebook=params.get("codebook"))
    w = scales.dequantize(qt, x.dtype)
    return _dot_rows(x, w)


def run_int4_pallas(spec, plan, params, x, *, k, precision=None,
                    epilogue=None, bias=None, residual=None):
    from repro.kernels import ops as kops

    m = params["scales"].shape[0]
    if spec.storage == "packed_u8":
        u8 = params["u8"]
    else:
        d = spec.resolve_d(k, m)
        u8 = packing.pack_storage(_linear._codes(params, spec, k, d))
    batch = x.shape[:-1]
    y = kops.int4_matmul(
        u8, params["scales"], x.reshape(-1, k).T,
        scale_block=spec.scale_block, interpret=plan.interpret,
        tm=plan.tm, tk=plan.tj, tb=plan.tb,
        acc_dtype=jnp.dtype(plan.acc_dtype), acc_in_vmem=plan.acc_in_vmem,
        epilogue=epilogue, bias=bias,
        residual=_residual_cols(residual, m))
    return y.T.reshape(*batch, -1).astype(_out_dtype(epilogue, x))


def run_msgemm_jnp(spec, plan, params, x, *, k, precision=None,
                   epilogue=None, bias=None, residual=None):
    m = params["scales"].shape[0]
    d = spec.resolve_d(k, m)
    codebook = params.get("codebook")
    batch = x.shape[:-1]
    xt = x.reshape(-1, k).T  # (k, B) — the paper's column layout
    lut_t = lut.produce(xt, d, dtype=jnp.float32, codebook=codebook)
    idx = params["idx"] if spec.storage == "packed_idx" else (
        packing.indices_from_storage(params["u8"], d, k))
    y = lut.consume(
        lut_t, idx, scales=params["scales"], scale_block=spec.scale_block,
        d=d, chunk=plan.consume_chunk)
    return y.T.reshape(*batch, -1).astype(x.dtype)


def run_msgemm_pallas(spec, plan, params, x, *, k, precision=None,
                      epilogue=None, bias=None, residual=None):
    from repro.kernels import ops as kops

    m = params["scales"].shape[0]
    d = spec.resolve_d(k, m)
    codes = _linear._codes(params, spec, k, d)
    batch = x.shape[:-1]
    y = kops.msgemm(
        codes, x.reshape(-1, k).T, d,
        scales=params["scales"], scale_block=spec.scale_block,
        codebook=params.get("codebook"), interpret=plan.interpret,
        tm=plan.tm, tj=plan.tj, tb=plan.tb,
        acc_dtype=jnp.dtype(plan.acc_dtype), acc_in_vmem=plan.acc_in_vmem,
        epilogue=epilogue, bias=bias,
        residual=_residual_cols(residual, m))
    return y.T.reshape(*batch, -1).astype(_out_dtype(epilogue, x))


def run_msgemm_mxu(spec, plan, params, x, *, k, precision=None,
                   epilogue=None, bias=None, residual=None):
    from repro.kernels import ops as kops

    m = params["scales"].shape[0]
    batch = x.shape[:-1]
    y = kops.msgemm_mxu(
        params["idx"], params["scales"], x.reshape(-1, k),
        spec.resolve_d(k, m), scale_block=spec.scale_block,
        interpret=plan.interpret, epilogue=epilogue, bias=bias,
        residual=None if residual is None else residual.reshape(-1, m))
    return y.reshape(*batch, m)


def run_dense_fallback(spec, plan, params, x, *, k, precision=None,
                       epilogue=None, bias=None, residual=None):
    """Dequantize to dense and matmul — numerically the quantization
    round-trip (same weights every other backend sees), executed on the
    plain MXU path.  The bottom rung of the degradation ladder: always
    available, no LUT/Pallas machinery to go wrong."""
    m = params["scales"].shape[0]
    d = spec.resolve_d(k, m)
    codes = _linear._codes(params, spec, k, d)
    qt = scales.QuantizedTensor(
        codes=codes, scales=params["scales"], block=spec.scale_block,
        shape=(codes.shape[0], k), codebook=params.get("codebook"))
    w = scales.dequantize(qt, x.dtype)
    return _dot_rows(x, w)


register_backend(
    "dense", modes=("bf16",), run=run_dense, priority=100,
    description="dense MXU matmul (the paper's naive GeMM, Eq. 14)")

# Last-resort safe path for quantized modes: priority below every
# specialized backend, selected only when the rest of the ladder is
# quarantined (NaN guard / watchdog escalation) or unavailable.
register_backend(
    "dense_fallback", modes=("msgemm", "int4_dequant"),
    run=run_dense_fallback, priority=-100,
    description="dequantize -> dense MXU matmul; quarantine-safe bottom "
                "rung of the degradation ladder (pallas -> jnp -> dense)")

register_backend(
    "msgemm_jnp", modes=("msgemm",), run=run_msgemm_jnp, priority=50,
    tunable=("consume_chunk",),
    description="produce/consume msGeMM in lowerable jnp (scan consume)")

# The paper's LUT kernel: on a TPU it outranks the scan formulation (and
# serves the learned codebooks msgemm_mxu leaves to it); everywhere else
# it only runs in interpret mode, so auto-selection demotes it below
# msgemm_jnp.
register_backend(
    "msgemm_pallas", modes=("msgemm",), run=run_msgemm_pallas,
    priority=lambda dev: 60 if dev == "tpu" else 40,
    tunable=("tm", "tj", "tb", "acc_in_vmem"),
    epilogue_ok=_pallas_epilogue_ok, partitionable=False,
    description="fused VMEM-tiled produce+consume Pallas kernel "
                "(amortized produce, VMEM acc stripe, fused epilogue)")

# The stored codes contracted on the MXU with factored f32 scales: on a
# TPU it outranks the LUT kernel, whose 16^d-entry gather runs on the
# vector unit; elsewhere it runs only in interpret mode, below both.
register_backend(
    "msgemm_mxu", modes=("msgemm",), run=run_msgemm_mxu,
    priority=lambda dev: 70 if dev == "tpu" else 30,
    storages=("packed_idx",), codebooks=("none",),
    epilogue_ok=_pallas_epilogue_ok, partitionable=False,
    description="stored int4 codes x activations on the MXU, per-block "
                "f32 scales, fused epilogue (kernels/msgemm_mxu)")

register_backend(
    "int4_jnp", modes=("int4_dequant",), run=run_int4_jnp, priority=50,
    description="dequantize -> MXU matmul (practical current-TPU path)")

register_backend(
    "int4_pallas", modes=("int4_dequant",), run=run_int4_pallas, priority=40,
    codebooks=("none",),  # the blocked kernel dequantizes the uniform grid
    tunable=("tm", "tj", "tb", "acc_in_vmem"),
    epilogue_ok=_pallas_epilogue_ok, partitionable=False,
    description="blocked dequant+dot Pallas kernel (kernels/int4_matmul)")
