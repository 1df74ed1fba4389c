"""Raw host callback primitive for the spool hooks (`repro.core.hostcb`).

`jax.experimental.io_callback`'s runtime impl wraps every operand in a
`jax.device_put(..., cpu_device0)` before invoking the python function.
On a multi-device CPU mesh that is a liveness hazard: the device_put of
a large operand takes jaxlib's *async* copy path, whose completion task
runs on the client's shared worker pool — the same pool the mesh's
collectives and intra-op work saturate. A callback that then forces the
array (`np.asarray`) parks its DEVICE thread on the pending event while
the other devices park at a collective waiting for this device: a
cross-device deadlock that reproduces reliably with 8 forced host
devices on a small container (and is timing-dependent everywhere else).

`raw_io_callback` is a ~60-line primitive that reuses jax's own
callback machinery — the same `_IOEffect` (so jit/scan treat it exactly
like `io_callback`: not DCE'd, allowed in control flow, droppable only
when result-free, which our token threading already prevents) and the
same MANUAL op-sharding under shard_map (one callback per device) — but
lowers through `emit_python_callback` directly, so the python
function receives the raw numpy VIEWS of the XLA operand buffers, no
jax arrays, no device_put, no events. Nothing in the callback can touch
the jax runtime, so nothing in the callback can deadlock it.

Contract (stricter than io_callback — the device_put was also a copy):

  * operand views are only valid DURING the call — the callback must
    copy anything it keeps (`np.array(x, copy=True)` is a plain memcpy);
  * results must be numpy arrays matching the declared ShapeDtypeStructs;
  * no vmap / differentiation through the primitive (the hooks never do
    either — it lives inside a custom_vjp's fwd/bwd).

The borrowed internals (`jax._src.callback.emit_python_callback`,
`_callback_op_sharding`, `_IOEffect`) are imported unguarded: if a jax
upgrade moves them, importing this module fails loudly rather than
falling back to a path that can deadlock a mesh.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

from jax._src import core as _jcore
from jax._src.callback import _callback_op_sharding, _IOEffect
from jax._src.callback import emit_python_callback
from jax._src.interpreters import mlir as _mlir
from jax._src.sharding_impls import SPMDAxisContext

raw_callback_p = _jcore.Primitive("repro_raw_host_callback")
raw_callback_p.multiple_results = True


@raw_callback_p.def_effectful_abstract_eval
def _raw_callback_abstract_eval(*avals, callback, result_avals):
    del avals, callback
    return result_avals, {_IOEffect}


def _raw_callback_lowering(ctx, *args, callback, result_avals):
    del result_avals

    def _wrapped(*flat_args):
        out = callback(*flat_args)
        return tuple(out) if isinstance(out, (tuple, list)) else (out,)

    axis_context = ctx.module_context.axis_context
    op_sharding = _callback_op_sharding(axis_context, None, ctx.avals_out)
    if ctx.module_context.platforms[0] == "tpu" and \
            isinstance(axis_context, SPMDAxisContext):
        # On TPU the callback is one send per operand and one recv per
        # result, and jax puts the same per-result sharding list on
        # every one of them, which the verifier rejects when the ranks
        # differ. `_callback_op_sharding` above has already refused a
        # body that leaves any mesh axis automatic, so this one is a
        # fully manual shard_map body: each device's ops are local, and
        # they are left unannotated.
        op_sharding = None
    result, _, _ = emit_python_callback(
        ctx, _wrapped, None, list(args), ctx.avals_in, ctx.avals_out,
        has_side_effect=True, returns_token=False, sharding=op_sharding)
    return result


# Not cacheable, as for jax's own io_callback: on TPU each lowered
# callback carries its own channel id.
_mlir.register_lowering(raw_callback_p, _raw_callback_lowering,
                        cacheable=False)


def raw_io_callback(callback: Callable[..., Any], result_shape_dtypes,
                    *args) -> Any:
    """`io_callback` minus the arg device_put (see module docstring).

    `result_shape_dtypes` is a flat sequence (or single) of
    ShapeDtypeStructs; returns a flat tuple (or single array). The
    callback receives numpy views valid only during the call.
    """
    single = hasattr(result_shape_dtypes, "shape")
    sds: Tuple = ((result_shape_dtypes,) if single
                  else tuple(result_shape_dtypes))

    # span per host invocation, named after the hook function — these
    # run on XLA's host-callback threads, so they are what ties the
    # device schedule to spool activity on the trace timeline
    from repro import obs
    span_name = "hostcb." + getattr(callback, "__name__", "cb")

    def traced_callback(*flat_args):
        with obs.span(span_name, cat="hostcb"):
            return callback(*flat_args)

    result_avals = tuple(
        _jcore.ShapedArray(tuple(s.shape), s.dtype) for s in sds)
    out = raw_callback_p.bind(*args, callback=traced_callback,
                              result_avals=result_avals)
    return out[0] if single else tuple(out)
