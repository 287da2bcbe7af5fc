"""Device time under any name scope the program opens, for the readers of
scopes that ``bench/scopes.py``'s fixed list leaves out (``attn/core``,
``attn/latent``, ``moe/shared``).  It reuses that module's decoder and
matching: a scope's time is the union of its ops' intervals, clipped to the
traced window, averaged over the devices and divided by the traced steps.
The trace is decoded once per run; readers share it through
``run.counters``.

A loop the compiler keeps (a scan of two or more layers) shows in the trace
as a ``while`` op whose event spans every op of its body; ``scopes.split``
counts that span as unscoped, over the body's own ops, so its scopes and
unscoped rest add up to more than the busy time.  The split without such
control-flow ops is logged beside it, once a run."""
from __future__ import annotations

from typing import Optional

from bench import scopes
from bench import trace as trace_lib

_OPS_KEY = "scope_time_ops"
# ops whose event spans the ops of the computations they call
CONTROL_FLOW = ("while", "call", "conditional")


def _ops(run, trace):
    if _OPS_KEY not in run.counters:
        ops = None
        if (trace is not None and trace.device_ids
                and run.counters.get("traced_steps", 0)
                and run.trace_path is not None):
            ops = scopes.decode(run.trace_path, trace.device_ids)
            _log_split_without_control_flow(run, trace, ops)
        run.counters[_OPS_KEY] = ops
    return run.counters[_OPS_KEY]


def _log_split_without_control_flow(run, trace, ops) -> None:
    flat = {d: [op for op in dev_ops
                if trace_lib.opcode(op.name) not in CONTROL_FLOW]
            for d, dev_ops in ops.items()}
    dropped = sum(len(v) for v in ops.values()) - sum(len(v)
                                                      for v in flat.values())
    sp = scopes.split(flat, trace.window, run.counters["traced_steps"])
    total = sum(sp.top.values()) + sp.unscoped
    run.log(f"[scope_time] without {dropped} control-flow op events "
            f"({', '.join(CONTROL_FLOW)}): {' + '.join(scopes.TOP)} + "
            f"unscoped {sp.unscoped:.4f} = {total:.4f} ms; busy "
            f"{sp.busy:.4f} ms")


def ms_per_step(run, trace, scope: str) -> Optional[float]:
    """Device milliseconds a traced step spends under ``scope``; ``None``
    with no trace, no traced step or no op under the scope."""
    ops = _ops(run, trace)
    if ops is None:
        return None
    lo, hi = trace.window
    total, found = 0.0, False
    paths = {}
    for dev_ops in ops.values():
        ivs = []
        for op in dev_ops:
            if op.tf_op not in paths:
                paths[op.tf_op] = scopes.in_scope(scopes.name_path(op.tf_op),
                                                  scope)
            if paths[op.tf_op]:
                ivs.append((op.start_ns, op.end_ns))
        found = found or bool(ivs)
        total += trace_lib.union_seconds(ivs, lo, hi)
    if not found:
        return None
    return total * 1e3 / (max(len(ops), 1) * run.counters["traced_steps"])
