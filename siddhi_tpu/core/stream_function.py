"""Stream functions / stream processors: multi-attribute-emitting chain stages.

Reference: query/processor/stream/function/StreamFunctionProcessor.java +
Pol2CartStreamFunctionProcessor.java (appends cartesian x/y), and
query/processor/stream/LogStreamProcessor.java (event tracing pass-through).
Custom ones register via @extension("stream_function", name): factory
`(params: list[CompiledExpr], schema_attrs, ref, scope) -> StreamFunctionStage`.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from siddhi_tpu.core.errors import SiddhiAppCreationError
from siddhi_tpu.core.event import EventBatch
from siddhi_tpu.core.executor import CompiledExpr, Env, Scope
from siddhi_tpu.core.flow import Flow
from siddhi_tpu.core.types import AttrType, PHYSICAL_DTYPE


class StreamFunctionStage:
    """Appends computed attribute columns to the flowing batch
    (reference: StreamFunctionProcessor.process attaching outputData)."""

    def __init__(
        self,
        ref: str,
        new_attrs: list[tuple[str, AttrType]],
        fn: Callable[[Env], dict[str, jnp.ndarray]],
    ):
        self.ref = ref
        self.new_attrs = new_attrs
        self.fn = fn

    def apply(self, flow: Flow) -> Flow:
        import dataclasses

        env = flow.env()
        new_cols = self.fn(env)
        cols = dict(flow.batch.cols)
        for name, t in self.new_attrs:
            col = jnp.broadcast_to(
                new_cols[name].astype(PHYSICAL_DTYPE[t]), flow.batch.valid.shape
            )
            cols[name] = col
        batch = EventBatch(flow.batch.ts, flow.batch.kind, flow.batch.valid, cols)
        return dataclasses.replace(flow, batch=batch)


class LogStage:
    """#log([priority,] message) — host-side event tracing via debug callback
    (reference: LogStreamProcessor)."""

    new_attrs: list = []

    def __init__(self, ref: str, message: str, stream_id: str):
        self.ref = ref
        self.message = message
        self.stream_id = stream_id

    def apply(self, flow: Flow) -> Flow:
        import logging

        from siddhi_tpu.utils.backend import host_callbacks_supported

        if not host_callbacks_supported():
            # backends whose probe rejects host callbacks: #log degrades
            # to a pass-through with a one-time notice
            if not getattr(self, "_warned", False):
                self._warned = True
                logging.getLogger(f"siddhi_tpu.log.{self.stream_id}").warning(
                    "#log disabled: this backend has no host callbacks"
                )
            return flow

        msg = self.message
        sid = self.stream_id

        def log_rows(valid, ts, kinds):
            import numpy as np

            n = int(np.asarray(valid).sum())
            if n:
                logging.getLogger(f"siddhi_tpu.log.{sid}").info(
                    "%s : %d event(s), ts=%s",
                    msg, n, np.asarray(ts)[np.asarray(valid)].tolist(),
                )

        jax.debug.callback(log_rows, flow.batch.valid, flow.batch.ts, flow.batch.kind)
        return flow


def make_stream_function(
    handler, schema_attrs: dict[str, AttrType], ref: str, scope: Scope, stream_id: str
):
    """Dispatch a #ns:name(params) handler to a built-in or extension stage."""
    from siddhi_tpu.core.executor import compile_expression
    from siddhi_tpu.core.extension import lookup
    from siddhi_tpu.query_api.expression import Constant

    name = (
        f"{handler.namespace}:{handler.name}" if handler.namespace else handler.name
    ).lower()

    if name == "log":
        msg = "LOG"
        for p in handler.parameters:
            if isinstance(p, Constant) and isinstance(p.value, str):
                msg = p.value
        return LogStage(ref, msg, stream_id)

    if name == "pol2cart":
        params = [compile_expression(p, scope) for p in handler.parameters]
        if len(params) not in (2, 3):
            raise SiddhiAppCreationError("pol2Cart(theta, rho[, z]) needs 2-3 args")

        def fn(env: Env, _p=params):
            theta = _p[0](env).astype(jnp.float32)
            rho = _p[1](env).astype(jnp.float32)
            out = {
                "x": rho * jnp.cos(jnp.deg2rad(theta)),
                "y": rho * jnp.sin(jnp.deg2rad(theta)),
            }
            if len(_p) > 2:
                out["z"] = _p[2](env).astype(jnp.float32)
            return out

        attrs = [("x", AttrType.DOUBLE), ("y", AttrType.DOUBLE)]
        if len(params) > 2:
            attrs.append(("z", AttrType.DOUBLE))
        return StreamFunctionStage(ref, attrs, fn)

    ext = lookup("stream_function", name) or lookup(
        "stream_processor", name
    )
    if ext is not None:
        params = [compile_expression(p, scope) for p in handler.parameters]
        return ext(params, schema_attrs, ref, scope)

    raise SiddhiAppCreationError(f"unknown stream function '#{name}'")


# ---------------------------------------------------------------------------
# script functions: define function f[python] return type { body }
# ---------------------------------------------------------------------------


def make_script_function(fdef):
    """Compile a `define function` body into an expression-compiler factory
    (reference: FunctionDefinition + script executors; the reference ships
    JavaScript/R/Scala via extensions — here the language is python, traced
    straight into the device program, so bodies must be jnp-compatible
    numeric/bool expressions over `data`)."""
    import textwrap

    lang = fdef.language.lower()
    if lang not in ("python", "py"):
        raise SiddhiAppCreationError(
            f"function '{fdef.id}': unsupported script language "
            f"'{fdef.language}' (python is built in)"
        )
    body = textwrap.dedent(fdef.body).strip()
    if "return" not in body:
        body = f"return {body}"
    src = "def __fn__(data):\n" + textwrap.indent(body, "    ")
    ns: dict = {}
    exec(src, {"jnp": jnp, "np": __import__("numpy")}, ns)
    raw = ns["__fn__"]
    rt = fdef.return_type

    def factory(params: list[CompiledExpr], scope: Scope) -> CompiledExpr:
        def fn(env: Env) -> jnp.ndarray:
            vals = [p(env) for p in params]
            return jnp.asarray(raw(vals)).astype(PHYSICAL_DTYPE[rt])

        return CompiledExpr(rt, fn)

    return factory
