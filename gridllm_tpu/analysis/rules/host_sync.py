"""host-sync-discipline: the engine's step/dispatch/ingest loops never
block on the device except at declared points (gridcheck v3, ISSUE 14).

The pipelined runner's whole design is that dispatch returns before the
device finishes and the ONE place a block is fetched is
``_fetch_oldest`` (plus ``_step_spec``'s serial verify fetch). A stray
``.item()`` / ``jax.device_get`` / ``np.asarray`` / ``block_until_ready``
anywhere else in those loops silently stalls the host against the
device every step — the step-time histograms from PR 4 can SEE the
stall (host_sched time balloons) but nothing prevented it. This rule
does, lexically:

Inside the engine's loop functions (``step``, ``_run``, ``_pump_once``,
``_step_spec``, ``_fetch_oldest``, ``_drain_ctl``, ``_try_admit``, and
every ``_dispatch_*`` / ``_ingest*``), the following are findings unless
the line carries a ``# sync-ok`` waiver (the declared sync points):

- ``.item()`` — one device round trip per call;
- ``jax.device_get(...)`` / ``jax.block_until_ready(...)`` /
  ``<x>.block_until_ready()`` — explicit sync;
- ``np.asarray(...)`` / ``np.array(...)`` — implicit transfer+sync when
  the argument is a device array (and in these loops it usually is);
- ``int(...)`` / ``float(...)`` applied to an expression that reads the
  engine's device-state attributes (``self.tokens`` / ``self.cache`` /
  ``self.active`` / ``self.counts`` / ``self.window`` / ``self.wlen`` /
  ``self.sampling``) — a python scalar conversion IS a sync.

A ``# sync-ok`` on a line the rule would not flag is itself a finding
(stale waivers rot into blanket permissions).

The mirror image of a stray sync is a stray LAUNCH (ISSUE 25): an eager
device program per call. ``jnp.int32(x)`` / ``jnp.bool_(x)`` /
``jnp.asarray(x)`` / ``jnp.array(x)`` on a host value and
``self.<device attr>.at[i].set(v)`` outside a jitted function each run a
one-element program (several, for ``.at``) with the chip idle behind the
Python and PJRT dispatch — forty of them were 28 ms of every admission.
In the same loop functions, plus ``_finish`` and ``apply_plan_op`` (the
other writers of per-slot device state), they are findings unless they
sit inside a nested jitted ``def``: build host arguments as numpy at
their final dtype and pass them straight to the jitted call, and write
device rows inside a donated program. No waiver: there is no case for
one in these functions.
"""

from __future__ import annotations

import ast
import re

from gridllm_tpu.analysis.core import Finding, Repo, dotted_name, rule

RULE = "host-sync-discipline"
ENGINE = "gridllm_tpu/engine/engine.py"
_WAIVER = "# sync-ok"
_LOOP_NAMES = (r"step|_run|_pump_once|_step_spec|_fetch_oldest|_drain_ctl|"
               r"_try_admit|_dispatch_\w+|_ingest\w*")
_LOOP_FN = re.compile(rf"^({_LOOP_NAMES})$")
_EAGER_FN = re.compile(rf"^({_LOOP_NAMES}|_finish|apply_plan_op)$")
_DEVICE_ATTRS = {"tokens", "cache", "active", "counts", "window", "wlen",
                 "sampling"}
_EAGER_CTORS = {"int32", "bool_", "asarray", "array"}


def _reads_device_state(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _DEVICE_ATTRS \
                and isinstance(sub.value, ast.Name) \
                and sub.value.id == "self":
            return True
    return False


def _flag_line(node: ast.Call) -> str | None:
    """The violation message for one call node, or None."""
    fn = dotted_name(node.func)
    leaf = fn.rsplit(".", 1)[-1]
    if leaf == "item" and not node.args and isinstance(node.func,
                                                      ast.Attribute):
        return ".item() — one device round trip per call"
    if fn.endswith("device_get"):
        return "jax.device_get — explicit device sync"
    if leaf == "block_until_ready":
        return "block_until_ready — explicit device sync"
    if fn in ("np.asarray", "np.array", "numpy.asarray", "numpy.array"):
        return f"{fn}() — implicit transfer+sync on device arrays"
    if isinstance(node.func, ast.Name) and node.func.id in ("int", "float") \
            and node.args and _reads_device_state(node.args[0]):
        return (f"{node.func.id}() on engine device state — a python "
                "scalar conversion is a sync")
    return None


def _is_jitted(fn: ast.AST) -> bool:
    """A nested def whose decorators mention jit (``@jax.jit``,
    ``@partial(jax.jit, donate_argnums=...)``): its body is traced."""
    return isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) and any(
        dotted_name(sub).rsplit(".", 1)[-1] == "jit"
        for dec in fn.decorator_list for sub in ast.walk(dec)
        if isinstance(sub, (ast.Name, ast.Attribute)))


def _eager_programs(fn: ast.AST):
    """(lineno, message) for every eager device program in `fn`'s body,
    nested jitted defs excluded."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if _is_jitted(node):
            continue
        stack.extend(ast.iter_child_nodes(node))
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            mod, _, leaf = name.rpartition(".")
            if mod in ("jnp", "jax.numpy") and leaf in _EAGER_CTORS:
                yield node.lineno, (
                    f"{name}() — its own device launch per call; pass "
                    "numpy at its final dtype to the jitted call")
        elif isinstance(node, ast.Attribute) and node.attr == "at" \
                and _reads_device_state(node.value):
            yield node.lineno, (
                ".at[] on engine device state outside a jitted def — "
                "several programs per write; do it in a donated program")


@rule(RULE, "no .item()/device_get/np.asarray/block_until_ready or "
            "scalar conversion of device state inside the engine "
            "step/dispatch/ingest loops, except at # sync-ok points; no "
            "eager jnp constructor or .at[] write of device state there")
def check(repo: Repo) -> list[Finding]:
    findings: list[Finding] = []
    f = repo.file(ENGINE)
    if f is None or f.tree is None:
        return findings
    lines = f.lines
    waiver_lines = {i for i, line in enumerate(lines, 1) if _WAIVER in line}
    used_waivers: set[int] = set()
    in_scope_lines: set[int] = set()

    for node in ast.walk(f.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _EAGER_FN.match(node.name):
            findings.extend(
                Finding(RULE, f.rel, lineno,
                        f"eager device program inside {node.name}(): {msg}")
                for lineno, msg in _eager_programs(node))
        if not _LOOP_FN.match(node.name):
            continue
        for sub in ast.walk(node):
            if hasattr(sub, "lineno"):
                in_scope_lines.add(sub.lineno)
            if not isinstance(sub, ast.Call):
                continue
            msg = _flag_line(sub)
            if msg is None:
                continue
            if sub.lineno in waiver_lines:
                used_waivers.add(sub.lineno)
                continue
            findings.append(Finding(
                RULE, f.rel, sub.lineno,
                f"host sync inside {node.name}(): {msg}; fetch through "
                "_fetch_oldest, or declare a deliberate sync point with "
                "# sync-ok"))

    for lineno in sorted(waiver_lines & in_scope_lines - used_waivers):
        findings.append(Finding(
            RULE, f.rel, lineno,
            "# sync-ok waiver on a line the rule does not flag — stale "
            "waiver, remove it"))
    return findings
