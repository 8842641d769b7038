"""armada-lint: AST rules for this repo's hard-won constraints.

Every rule here encodes a constraint that was PAID for -- a measured
regression, a debugging session, or a parity break (CLAUDE.md; docs/lint.md
has the catalogue with the numbers).  The analyzer is stdlib-``ast`` only,
so it runs anywhere the repo does, with no new dependencies.

Suppressions are per-line and must carry a reason::

    x = jnp.argmin(masked)  # lint: allow(full-argmin) -- [B]-block, not [N]

The comment may sit on any line the flagged statement spans, or on the line
directly above it.  ``allow(rule-a, rule-b)`` suppresses several rules at
once; an allow WITHOUT a reason is itself a violation
(``allow-missing-reason``), so the tree stays self-documenting.

Entry points: :func:`lint_source` (one buffer, used by the fixture tests),
:func:`lint_file`, :func:`lint_tree` (the CI walk; ``tools/lint.py`` wraps
it).  Rules register through :func:`rule`; each declares a path scope so
kernel rules never fire on host code and vice versa.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import Callable, Iterable, Optional

from armada_tpu.analysis import dataflow as _df

# --------------------------------------------------------------------------
# findings + suppressions
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Finding:
    rule: str
    path: str  # repo-relative, posix separators
    line: int
    col: int
    message: str
    end_line: int = 0  # last line of the flagged statement (0 = same as line)

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# `# lint: allow(rule-a, rule-b) -- reason`
_ALLOW_RE = re.compile(
    r"#\s*lint:\s*allow\(\s*([A-Za-z0-9_\-, ]+?)\s*\)\s*(?:--\s*(\S.*))?$"
)


def _comment_lines(text: str) -> list[tuple[int, str]]:
    """(lineno, text) for real COMMENT tokens only: an allow marker inside
    a string literal is data, not a suppression (and must not pollute the
    --stats census).  Falls back to a raw line scan if tokenization fails
    -- callers have already ast-parsed the buffer, so that is rare."""
    try:
        toks = tokenize.generate_tokens(io.StringIO(text).readline)
        return [(t.start[0], t.string) for t in toks if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return list(enumerate(text.splitlines(), start=1))


def _parse_suppressions(text: str) -> tuple[dict, list, list]:
    """Per-line allow map {lineno: set(rules)}, findings for reasonless
    allows, and (lineno, rules, reason) records for the suppression census
    (tools/lint.py --stats).  Line numbers are 1-based to match ast."""
    allows: dict[int, set] = {}
    bad: list[tuple[int, str]] = []
    records: list[tuple[int, frozenset, str]] = []
    for i, comment in _comment_lines(text):
        m = _ALLOW_RE.search(comment)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        reason = (m.group(2) or "").strip()
        if not reason:
            bad.append((i, ", ".join(sorted(rules))))
            continue
        allows.setdefault(i, set()).update(rules)
        records.append((i, frozenset(rules), reason))
    return allows, bad, records


# --------------------------------------------------------------------------
# source model
# --------------------------------------------------------------------------

class Source:
    """One parsed buffer: tree + parent links + suppression map."""

    def __init__(self, text: str, relpath: str):
        self.text = text
        self.relpath = relpath.replace(os.sep, "/")
        self.lines = text.splitlines()
        self.tree = ast.parse(text)
        self.allows, self.reasonless_allows, _ = _parse_suppressions(text)
        self._parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def enclosing_function(self, node: ast.AST):
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parent(cur)
        return None

    def suppressed(self, rule_name: str, node: ast.AST) -> bool:
        """An allow on any line the node spans, or in the comment block
        sitting DIRECTLY above the flagged line (blank/comment lines only
        in between) -- never across intervening code, so an allow cannot
        leak onto the next statement."""
        lo = getattr(node, "lineno", 0)
        hi = getattr(node, "end_lineno", lo) or lo
        for line in range(max(1, lo), hi + 1):
            if rule_name in self.allows.get(line, ()):
                return True
        m = lo - 1
        while m >= 1:
            text = self.lines[m - 1].strip()
            if text and not text.startswith("#"):
                break
            if rule_name in self.allows.get(m, ()):
                return True
            m -= 1
        return False


# --------------------------------------------------------------------------
# rule registry
# --------------------------------------------------------------------------

@dataclasses.dataclass
class LintRule:
    name: str
    summary: str
    scope: Callable[[str], bool]
    check: Callable[[Source], Iterable[Finding]]


RULES: list[LintRule] = []


def anywhere(_relpath: str) -> bool:
    return True


def under(*prefixes: str) -> Callable[[str], bool]:
    return lambda p: p.startswith(prefixes)


def in_files(*files: str) -> Callable[[str], bool]:
    fset = set(files)
    return lambda p: p in fset


def rule(name: str, summary: str, scope: Callable[[str], bool] = anywhere):
    def deco(fn):
        RULES.append(LintRule(name, summary, scope, fn))
        return fn

    return deco


def rule_names() -> list[str]:
    return [r.name for r in RULES]


# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------

def _dotted(node: ast.AST) -> str:
    """`a.b.c` for Attribute/Name chains, '' for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_full_slice(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Slice)
        and node.lower is None
        and node.upper is None
        and node.step is None
    )


def _calls_in(node: ast.AST, names: set) -> Iterable[ast.Call]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _dotted(sub.func) in names:
            yield sub


def _finding(src: Source, name: str, node: ast.AST, msg: str) -> Finding:
    return Finding(
        name,
        src.relpath,
        node.lineno,
        node.col_offset,
        msg,
        end_line=getattr(node, "end_lineno", node.lineno) or node.lineno,
    )


def _at_scatter(call: ast.Call):
    """(subscript, index, method) when `call` is `<x>.at[<index>].<method>(...)`,
    else None.  Matches any scatter method (set/add/mul/min/max/...)."""
    f = call.func
    if not isinstance(f, ast.Attribute):
        return None
    sub = f.value
    if not (
        isinstance(sub, ast.Subscript)
        and isinstance(sub.value, ast.Attribute)
        and sub.value.attr == "at"
    ):
        return None
    return sub, sub.slice, f.attr


# --------------------------------------------------------------------------
# kernel rules (armada_tpu/models/)
# --------------------------------------------------------------------------

_MODELS = under("armada_tpu/models/")


def _is_static_loop_var(fn, tree, name: str) -> bool:
    """True if `name` is the target of a `for name in range(...)` in the
    enclosing scope -- a trace-time python int, i.e. a static unroll."""
    scope = fn if fn is not None else tree
    for node in ast.walk(scope):
        if (
            isinstance(node, ast.For)
            and isinstance(node.target, ast.Name)
            and node.target.id == name
            and isinstance(node.iter, ast.Call)
            and _dotted(node.iter.func) in ("range", "reversed")
        ):
            return True
    return False


@rule(
    "axis1-scatter",
    "axis-1 vector-index scatter (`.at[:, idx].set`) copies the whole "
    "buffer on XLA:CPU (~128us for [S,N], measured round 3); keep caches "
    "FLAT with leading-dim index vectors",
    scope=_MODELS,
)
def _axis1_scatter(src: Source):
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        hit = _at_scatter(node)
        if hit is None:
            continue
        _sub, index, _method = hit
        if not isinstance(index, ast.Tuple) or not index.elts:
            continue
        if not _is_full_slice(index.elts[0]):
            continue
        # `.at[:, 0]` (constant scalar lane) keeps the copy bounded, and a
        # python loop variable over range() is a static unroll -- each
        # unrolled step is a constant lane too.  A vector/traced index is
        # the measured full-buffer copy.
        fn = src.enclosing_function(node)
        for elt in index.elts[1:]:
            if _is_full_slice(elt) or isinstance(elt, ast.Constant):
                continue
            if isinstance(elt, ast.Name) and _is_static_loop_var(
                fn, src.tree, elt.id
            ):
                continue
            yield _finding(
                src,
                "axis1-scatter",
                node,
                "axis-1 vector-index scatter copies the whole buffer on "
                "XLA:CPU; restructure the cache flat with a leading-dim "
                "index vector (CLAUDE.md round-3 kernel economics)",
            )
            break


@rule(
    "full-argmin",
    "argmin/argmax in the round kernel is a SCALAR loop on XLA:CPU "
    "(~190us at N=51k); use the blocked-minima path ([N/B] row + one [B] "
    "block) or annotate the scanned axis",
    scope=in_files("armada_tpu/models/fair_scheduler.py"),
)
def _full_argmin(src: Source):
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in ("argmin", "argmax"):
            yield _finding(
                src,
                "full-argmin",
                node,
                f"{f.attr} in the round kernel: XLA:CPU lowers it to a "
                "scalar loop -- use the blocked-minima pattern for [N]-sized "
                "operands, or allow() naming the (small) axis scanned",
            )


@rule(
    "f64-score",
    "f64 creeping into kernel score arithmetic flips near-ties against the "
    "sequential oracle (parity lesson: f32 score/cost arithmetic is part of "
    "the reference semantics)",
    scope=in_files("armada_tpu/models/fair_scheduler.py"),
)
def _f64_score(src: Source):
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Attribute) and node.attr == "float64":
            yield _finding(
                src,
                "f64-score",
                node,
                "float64 in the round kernel: score/cost arithmetic must "
                "stay f32 (raw f64 flips near-ties vs the oracle); integral "
                "capacity math belongs in the host builder, not here",
            )
        elif isinstance(node, ast.Constant) and node.value == "float64":
            yield _finding(
                src,
                "f64-score",
                node,
                "'float64' dtype string in the round kernel (see f64-score)",
            )
        elif (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "astype"
            and node.args
            and isinstance(node.args[0], ast.Name)
            and node.args[0].id == "float"
        ):
            yield _finding(
                src,
                "f64-score",
                node,
                "astype(float) is float64 in the round kernel (see f64-score)",
            )


@rule(
    "fetch-not-barrier",
    "production sync is a real scalar fetch (copy_to_host_async + "
    "np.asarray) of data the host needs anyway, never a bare "
    "jax.block_until_ready barrier (what either costs on this host is "
    "not measured -- ROADMAP S5)",
    scope=under("armada_tpu/"),
)
def _fetch_not_barrier(src: Source):
    for node in ast.walk(src.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "block_until_ready"
        ):
            yield _finding(
                src,
                "fetch-not-barrier",
                node,
                "block_until_ready is not this repo's barrier: "
                "synchronize with an actual device->host fetch of a scalar "
                "or the compact result instead",
            )


# --------------------------------------------------------------------------
# host rules
# --------------------------------------------------------------------------

def _name_assigned_from_call(fn: Optional[ast.AST], tree: ast.AST, name: str) -> bool:
    """True if `name` is (re)bound from a Call in the enclosing function (or
    module when `fn` is None) -- the repo's coercion idiom is
    `v = col.dtype.type(v)` / `v = dt(v)`, always a Call."""
    scope = fn if fn is not None else tree
    for node in ast.walk(scope):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    return True
                if isinstance(tgt, ast.Tuple) and any(
                    isinstance(e, ast.Name) and e.id == name for e in tgt.elts
                ):
                    return True
    return False


@rule(
    "searchsorted-dtype",
    "np.searchsorted with a probe whose dtype mismatches the column "
    "promotes-and-COPIES the whole column (~230us/call at 300k rows, "
    "round 2); coerce with `col.dtype.type(v)`",
)
def _searchsorted_dtype(src: Source):
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "searchsorted"):
            continue
        base = _dotted(f.value)
        if base in ("np", "numpy", "jnp"):
            probe = node.args[1] if len(node.args) > 1 else None
        else:
            probe = node.args[0] if node.args else None  # col.searchsorted(v)
        if probe is None:
            continue
        # Calls (any coercion/cast), constants, subscripts of same-table
        # arrays and binops on them are same-dtype by construction; a bare
        # Name is only trusted when the enclosing scope rebinds it from a
        # Call (the `v = dt(v)` idiom).
        if isinstance(probe, ast.Name):
            if _name_assigned_from_call(
                src.enclosing_function(node), src.tree, probe.id
            ):
                continue
        elif not isinstance(probe, ast.Attribute):
            continue
        yield _finding(
            src,
            "searchsorted-dtype",
            node,
            "searchsorted probe is not visibly dtype-coerced: a mismatched "
            "probe promotes-and-copies the whole column -- wrap it in "
            "`col.dtype.type(...)` (or allow() stating why dtypes match)",
        )


@rule(
    "fixed-sleep-retry",
    "a constant time.sleep inside a retry loop (loop body containing "
    "try/except) synchronizes every waiter onto the recovering peer; use "
    "core/backoff.Backoff (full jitter)",
)
def _fixed_sleep_retry(src: Source):
    for node in ast.walk(src.tree):
        if not isinstance(node, (ast.While, ast.For)):
            continue
        has_try = any(
            isinstance(sub, ast.Try)
            for stmt in node.body
            for sub in ast.walk(stmt)
        )
        if not has_try:
            continue  # poll loop, not a retry loop
        for stmt in node.body:
            for call in _calls_in(stmt, {"time.sleep", "sleep"}):
                if call.args and isinstance(call.args[0], ast.Constant):
                    yield _finding(
                        src,
                        "fixed-sleep-retry",
                        call,
                        "constant sleep in a retry loop retries in lockstep "
                        "with every other waiter -- use "
                        "core/backoff.Backoff.next_delay() (full jitter)",
                    )


@rule(
    "bare-except",
    "`except:` swallows KeyboardInterrupt/SystemExit and hides the "
    "exception type from the reader; name the exception (Exception at "
    "broadest) or re-raise",
)
def _bare_except(src: Source):
    for node in ast.walk(src.tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield _finding(
                src,
                "bare-except",
                node,
                "bare `except:` also catches KeyboardInterrupt/SystemExit; "
                "catch Exception (or narrower) instead",
            )


@rule(
    "wallclock-event-order",
    "wall-clock reads (time.time / datetime.now) in event-sourced modules: "
    "event order comes from the log sequence; wall clocks skew across "
    "hosts and move backwards",
    scope=under("armada_tpu/eventlog/", "armada_tpu/jobdb/", "armada_tpu/events/"),
)
def _wallclock_event_order(src: Source):
    bad = {
        "time.time",
        "time.time_ns",
        "datetime.now",
        "datetime.utcnow",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
    }
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in bad:
            yield _finding(
                src,
                "wallclock-event-order",
                node,
                "wall-clock call in an event-sourced module: ordering must "
                "come from the log sequence (use the injected clock for "
                "timestamps, time.monotonic for intervals)",
            )


_SLO_MODULES = (
    "armada_tpu/ops/metrics.py",
    "armada_tpu/scheduler/slo.py",
    # The cycle-trace recorder: span timestamps feed the same latency
    # surfaces (stage histograms, bench stage_*_s, Perfetto timelines), so
    # a second clock source here would skew every correlated view.
    "armada_tpu/ops/trace.py",
)


def _slo_scope(p: str) -> bool:
    return p.startswith("armada_tpu/loadgen/") or p in _SLO_MODULES


@rule(
    "slo-wallclock",
    "clock reads in the SLO/loadgen modules outside the named mono_now() "
    "helper: SLO latency math must ride ONE monotonic source -- wall "
    "clocks skew and step backwards, and two clock sources in one "
    "latency subtraction produce negative or fictional tails",
    scope=_slo_scope,
)
def _slo_wallclock(src: Source):
    banned = {
        "time.time",
        "time.time_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "datetime.now",
        "datetime.utcnow",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
    }
    for node in ast.walk(src.tree):
        if not (isinstance(node, ast.Call) and _dotted(node.func) in banned):
            continue
        fn = src.enclosing_function(node)
        if fn is not None and fn.name == "mono_now":
            continue  # the single sanctioned definition site
        yield _finding(
            src,
            "slo-wallclock",
            node,
            "clock read in an SLO/loadgen module: route every timestamp "
            "through ops/metrics.mono_now() (the one monotonic source); "
            "wall clocks here turn latency histograms into fiction",
        )


@rule(
    "grpc-options",
    "gRPC channels/servers built without the shared transport options "
    "(rpc.transport): raising limits on only one side still breaks >4MB "
    "lease batches (round-8 lesson, tests/test_rpc.py pins both sides)",
    scope=under("armada_tpu/"),
)
def _grpc_options(src: Source):
    if src.relpath in (
        "armada_tpu/rpc/transport.py",  # defines the options
    ):
        return
    targets = {
        "grpc.insecure_channel",
        "grpc.secure_channel",
        "grpc.server",
        "grpc.aio.insecure_channel",
        "grpc.aio.secure_channel",
        "grpc.aio.server",
    }
    for node in ast.walk(src.tree):
        if not (isinstance(node, ast.Call) and _dotted(node.func) in targets):
            continue
        ok = False
        for kw in node.keywords:
            if kw.arg == "options":
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Call) and _dotted(sub.func).split(
                        "."
                    )[-1] in ("server_options", "channel_options"):
                        ok = True
        if not ok:
            yield _finding(
                src,
                "grpc-options",
                node,
                "gRPC channel/server without options=server_options()/"
                "channel_options(): message caps + keepalive must match on "
                "BOTH sides (rpc/transport.py)",
            )


@rule(
    "thread-no-daemon",
    "threading.Thread without an explicit daemon= : a wedged non-daemon "
    "thread (a hung device call) blocks interpreter exit forever",
    scope=under("armada_tpu/"),
)
def _thread_no_daemon(src: Source):
    for node in ast.walk(src.tree):
        if not (
            isinstance(node, ast.Call)
            and _dotted(node.func) in ("threading.Thread", "Thread")
        ):
            continue
        if not any(kw.arg == "daemon" for kw in node.keywords):
            yield _finding(
                src,
                "thread-no-daemon",
                node,
                "threading.Thread without explicit daemon=: a thread wedged "
                "on a dead backend must not block process exit -- say "
                "daemon=True, or daemon=False with an allow() explaining "
                "the join discipline",
            )


@rule(
    "lock-held-sleep",
    "time.sleep while holding a lock: every other thread (including the "
    "watchdog's failover path) stalls behind the sleeper",
)
def _lock_held_sleep(src: Source):
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.With):
            continue
        holds_lock = any(
            "lock" in _dotted(item.context_expr).lower()
            or (
                isinstance(item.context_expr, ast.Call)
                and "lock" in _dotted(item.context_expr.func).lower()
            )
            for item in node.items
        )
        if not holds_lock:
            continue
        for stmt in node.body:
            for call in _calls_in(stmt, {"time.sleep"}):
                yield _finding(
                    src,
                    "lock-held-sleep",
                    call,
                    "sleeping while holding a lock stalls every waiter "
                    "(the watchdog failover path contends these locks); "
                    "sleep outside the critical section",
                )


@rule(
    "mutable-default-arg",
    "mutable default argument ([], {}, set()): shared across calls, a "
    "classic aliasing bug",
)
def _mutable_default_arg(src: Source):
    for node in ast.walk(src.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for default in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(default, ast.Call)
                and _dotted(default.func) in ("list", "dict", "set")
            ):
                yield _finding(
                    src,
                    "mutable-default-arg",
                    default,
                    "mutable default argument is shared across calls; "
                    "default to None and construct inside",
                )


# --------------------------------------------------------------------------
# event-sourcing rules
# --------------------------------------------------------------------------

# DB fetch cursors may only advance with a committed JobDb txn
# (scheduler/scheduler.py _cycle: cursors0 save + abort rewind); consumer
# positions commit transactionally with their batch (ingest/pipeline.py).
_CURSOR_FIELDS = {"_jobs_serial", "_runs_serial"}
_CURSOR_OWNERS = {"armada_tpu/scheduler/scheduler.py"}
_POSITION_OWNERS = {
    "armada_tpu/eventlog/publisher.py",  # Consumer.ack / reset
    "armada_tpu/ingest/pipeline.py",  # ack only after the store committed
}


@rule(
    "cursor-outside-txn",
    "DB fetch cursors (_jobs_serial/_runs_serial) and consumer positions "
    "may only move inside the txn-commit helpers; an out-of-band write "
    "skips or replays batches",
    scope=under("armada_tpu/"),
)
def _cursor_outside_txn(src: Source):
    for node in ast.walk(src.tree):
        targets: list[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for tgt in targets:
            for sub in ast.walk(tgt):
                if (
                    isinstance(sub, ast.Attribute)
                    and sub.attr in _CURSOR_FIELDS
                    and src.relpath not in _CURSOR_OWNERS
                ):
                    yield _finding(
                        src,
                        "cursor-outside-txn",
                        node,
                        f"write to fetch cursor `{sub.attr}` outside "
                        "scheduler/scheduler.py: cursors only advance with "
                        "a committed txn (abort must rewind them)",
                    )
        # consumer-position advance: Consumer.ack()/positions mutation
        if isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr == "ack"
                and "consumer" in _dotted(f.value).lower()
                and src.relpath not in _POSITION_OWNERS
            ):
                yield _finding(
                    src,
                    "cursor-outside-txn",
                    node,
                    "consumer position ack outside the ingestion pipeline: "
                    "positions commit transactionally with their batch "
                    "(ingest/pipeline.py)",
                )


_QV_OWNERS = {
    "armada_tpu/jobdb/job.py",  # the lease/requeue transition helpers
    "armada_tpu/ingest/schedulerdb.py",  # version-guarded UPDATE
    "armada_tpu/ingest/dbops.py",  # row merge carries the version
    "armada_tpu/scheduler/reconciliation.py",  # version-guard row merge
}


@rule(
    "queued-version-write",
    "queued_version written outside the lease path: queued/lease state is "
    "guarded by queued_version (the lease event carries "
    "update_sequence_number); an out-of-band bump desyncs requeue "
    "protection",
    scope=under("armada_tpu/"),
)
def _queued_version_write(src: Source):
    if src.relpath in _QV_OWNERS:
        return
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "queued_version":
                    yield _finding(
                        src,
                        "queued-version-write",
                        node,
                        "queued_version passed outside the jobdb/ingest "
                        "lease path: the version guard only stays sound "
                        "when every bump rides a lease/requeue transition",
                    )
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) else [node.target]
            for tgt in tgts:
                if isinstance(tgt, ast.Attribute) and tgt.attr == "queued_version":
                    yield _finding(
                        src,
                        "queued-version-write",
                        node,
                        "direct queued_version attribute write: Jobs are "
                        "immutable; versions move via the jobdb transition "
                        "helpers only",
                    )


# Sanctioned owners of explicit device placement: the slab caches and the
# mesh subsystem place problem arrays WITH their shardings; everywhere else
# a bare device_put re-places the array onto one device -- for a node-axis-
# sharded slab that is a silent full gather onto one chip's HBM.
_MESH_OWNERS = ("armada_tpu/parallel/",)
_MESH_OWNER_FILES = {"armada_tpu/models/slab.py"}


def _mesh_gather_scope(p: str) -> bool:
    return (
        p.startswith("armada_tpu/")
        and not p.startswith(_MESH_OWNERS)
        and p not in _MESH_OWNER_FILES
    )


@rule(
    "mesh-gather",
    "jax.device_put / .addressable_data on problem arrays outside the slab "
    "cache + parallel/ owners: a bare placement silently GATHERS a node-"
    "axis-sharded slab onto one chip (mesh serving plane, round 12)",
    scope=_mesh_gather_scope,
)
def _mesh_gather(src: Source):
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call) and _dotted(node.func) == "jax.device_put":
            yield _finding(
                src,
                "mesh-gather",
                node,
                "explicit device placement outside models/slab.py + "
                "parallel/: on the mesh serving plane this gathers a "
                "sharded slab onto one device -- route uploads through the "
                "device cache (DeviceDeltaCache/MeshDeviceDeltaCache), or "
                "allow() stating why the placement is mesh-safe",
            )
        elif isinstance(node, ast.Attribute) and node.attr == "addressable_data":
            yield _finding(
                src,
                "mesh-gather",
                node,
                ".addressable_data() reads ONE shard of a sharded array -- "
                "on the serving path that is a partial (wrong) view of the "
                "slab; fetch through the compact decode, or allow() naming "
                "the single-device invariant",
            )


# The one sanctioned tmp+fsync+rename implementation.
_STATEFILE_OWNER = "armada_tpu/core/statefile.py"


@rule(
    "atomic-state-file",
    "os.replace/os.rename outside core/statefile.py: a hand-rolled "
    "atomic-write keeps missing a step (file fsync, DIRECTORY fsync, "
    "checksum) -- every cursor/snapshot/election file write rides the "
    "shared helper",
    scope=under("armada_tpu/"),
)
def _atomic_state_file(src: Source):
    if src.relpath == _STATEFILE_OWNER:
        return
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in (
            "os.replace",
            "os.rename",
        ):
            yield _finding(
                src,
                "atomic-state-file",
                node,
                "hand-rolled atomic rename: durable state files (cursors, "
                "snapshots, election records) go through core/statefile.py "
                "(tmp + fsync + rename + directory fsync, checksummed "
                "envelope for snapshots) -- the pre-refactor lease write "
                "missed the directory fsync",
            )


# --------------------------------------------------------------------------
# dataflow rules (armada-lint v2)
#
# These query the provenance lattice in analysis/dataflow.py instead of
# matching node shapes: every one of them separates a true positive from a
# syntactically IDENTICAL near miss (tests/test_lint.py pins the twin-shape
# property), which is exactly what the per-node rules above cannot do.
# --------------------------------------------------------------------------

_KERNEL_DF = under("armada_tpu/models/", "armada_tpu/parallel/")

# The hoisting/copy hazards are arithmetic, not boolean masking: the
# kernel's sanctioned fit gates (`static_ok & p.node_ok & ~banned`) are
# bitwise ops over gathered rows and stay exempt by construction.
_ARITH_OPS = (
    ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv, ast.Mod, ast.Pow,
    ast.MatMult,
)


def _loop_body_analyses(ma) -> Iterable:
    """Every resolved while/fori body analysis (+ their nested defs)."""
    for site in ma.loop_sites():
        for body in site.bodies:
            yield from body.tree()


@rule(
    "gathered-row-compute",
    "arithmetic inside a lax.while_loop/fori_loop body combining a gathered "
    "row with a whole loop-invariant buffer, with no carry dependence: XLA "
    "cannot hoist it and recomputes O(N) work per iteration (a single "
    "in-loop mask multiply cost 6x, round 1) -- precompute the [G,R] table "
    "outside and gather one row",
    scope=_KERNEL_DF,
)
def _gathered_row_compute(src: Source):
    if "while_loop" not in src.text and "fori_loop" not in src.text:
        return
    ma = _df.of(src)
    seen: set = set()
    for fa in _loop_body_analyses(ma):
        fn = fa.fn
        root = fn if not isinstance(fn, ast.Lambda) else fn.body
        for node in ast.walk(root):
            if not (
                isinstance(node, ast.BinOp) and isinstance(node.op, _ARITH_OPS)
            ):
                continue
            key = (node.lineno, node.col_offset)
            if key in seen:
                continue
            lt, rt = fa.tags(node.left), fa.tags(node.right)
            if _df.CARRY in (lt | rt):
                continue  # depends on loop state: not precomputable
            for g, w in ((lt, rt), (rt, lt)):
                # PY on the gathered side = index arithmetic (key % S),
                # not a table recompute.
                if (
                    _df.GATHER in g
                    and _df.PY not in g
                    and _df.WHOLE in w
                    and _df.EXT in w
                ):
                    seen.add(key)
                    yield _finding(
                        src,
                        "gathered-row-compute",
                        node,
                        "in-loop arithmetic combines a gathered row with a "
                        "whole loop-invariant buffer and no carry "
                        "dependence: XLA cannot hoist it -- precompute the "
                        "combined table outside the loop and gather one "
                        "row (CLAUDE.md: the 6x mask-multiply lesson)",
                    )
                    break


@rule(
    "branch-return-array",
    "a lax.cond/switch branch returns a value with whole-buffer provenance: "
    "threading big arrays through BRANCH RETURNS copies them per iteration "
    "(round-3 measured) -- pass rows out and write back outside the switch",
    scope=_KERNEL_DF,
)
def _branch_return_array(src: Source):
    if "lax.cond" not in src.text and "lax.switch" not in src.text:
        return
    ma = _df.of(src)
    seen: set = set()
    sites = []
    for fa in ma.module_fa.tree():
        sites.extend(fa.branch_sites)
    for fa in _loop_body_analyses(ma):
        sites.extend(fa.branch_sites)
    for site in sites:
        key = (site.call.lineno, site.call.col_offset)
        if key in seen:
            continue
        for br in site.branches:
            if _df.WHOLE not in br.return_tags:
                continue
            seen.add(key)
            name = getattr(br.fn, "name", "<lambda>")
            yield _finding(
                src,
                "branch-return-array",
                site.call,
                f"branch `{name}` returns a whole input buffer through "
                "lax.cond/switch: branch returns copy the buffer per "
                "iteration -- return the touched row(s) and write back "
                "outside the switch (CLAUDE.md round-3 kernel economics)",
            )
            break


@rule(
    "inloop-scatter-gathered-key",
    "an in-loop `.at[...].set/add` into a loop-INVARIANT whole buffer whose "
    "index is tainted by the gathered candidate: each iteration builds a "
    "fresh O(N) copy (the ban-mask lesson) -- ride a precomputed row table "
    "(`ban_mask[BR,N]` + a `g_ban_row[G]` gather) instead",
    scope=_KERNEL_DF,
)
def _inloop_scatter_gathered_key(src: Source):
    if "while_loop" not in src.text and "fori_loop" not in src.text:
        return
    ma = _df.of(src)
    seen: set = set()
    for fa in _loop_body_analyses(ma):
        for sc in fa.scatters:
            key = (sc.call.lineno, sc.call.col_offset)
            if key in seen:
                continue
            if (
                _df.GATHER in sc.index_tags
                and _df.CARRY not in sc.base_tags
                and _df.WHOLE in sc.base_tags
            ):
                seen.add(key)
                yield _finding(
                    src,
                    "inloop-scatter-gathered-key",
                    sc.call,
                    "in-loop scatter into a loop-invariant buffer keyed on "
                    "the gathered candidate: XLA materializes a fresh "
                    "full-buffer copy every iteration -- precompute the "
                    "row table outside and gather (carry-state scatters "
                    "with reduced indices stay exempt)",
                )


@rule(
    "commit-scatter-gathered-old",
    "an in-loop commit scatter keyed on gathered candidates re-reads its own "
    "base buffer at the gathered lanes (`x.at[idx].set(where(ok, v, "
    "x[idx]))`): batched dummy lanes sharing a real index race the true "
    "write (the round-3 double-placed gang 0) -- scatter a CONSTANT value "
    "with dummy lanes pushed out of range and mode='drop'",
    scope=_KERNEL_DF,
)
def _commit_scatter_gathered_old(src: Source):
    if "while_loop" not in src.text and "fori_loop" not in src.text:
        return
    ma = _df.of(src)
    seen: set = set()
    for fa in _loop_body_analyses(ma):
        for sc in fa.scatters:
            if sc.method != "set":
                continue
            key = (sc.call.lineno, sc.call.col_offset)
            if key in seen:
                continue
            if (
                _df.GATHER not in sc.index_tags
                or _df.CARRY not in sc.base_tags
            ):
                continue
            base_name = _dotted(sc.base)
            if not base_name:
                continue
            for arg in sc.call.args:
                hit = None
                for node in ast.walk(arg):
                    # the old-value read: a gather of the SCATTERED base
                    # itself, indexed by tainted (gathered) lanes
                    if (
                        isinstance(node, ast.Subscript)
                        and _dotted(node.value) == base_name
                        and _df.CARRY in fa.tags(node.value)
                        and _df.GATHER in fa.tags(node.slice)
                    ):
                        hit = node
                        break
                if hit is not None:
                    seen.add(key)
                    yield _finding(
                        src,
                        "commit-scatter-gathered-old",
                        sc.call,
                        "commit scatter keyed on gathered candidates reads "
                        "its own base back at the scattered lanes: with "
                        "batched lanes, masked-out dummies sharing a real "
                        "index race the true write -- scatter a constant "
                        "with mode='drop' and out-of-range dummy indices "
                        "(single-lane scalar commits carry a reasoned "
                        "allow: one lane cannot lane-race)",
                    )
                    break


def _jit_bound_names(src: Source, site) -> set:
    """Names a `jax.jit(f)` result is bound to, or the decorated def name."""
    names: set = set()
    if site.fn is not None and site.node in getattr(
        site.fn, "decorator_list", ()
    ):
        names.add(site.fn.name)  # decorated def: callers use its own name
    elif isinstance(site.node, ast.Call):
        parent = src.parent(site.node)
        if isinstance(parent, ast.Assign):
            for tgt in parent.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
    return names


@rule(
    "unpinned-out-shardings",
    "a jax.jit program is fed a mesh-sharded value but the jit call does "
    "not pin out_shardings: GSPMD left to choose may GATHER the sharded "
    "slab onto one chip while scattering into it (round 12's silent slab "
    "gather) -- pin the output layout (slab._make_apply(out_shardings=...))",
    scope=under("armada_tpu/"),
)
def _unpinned_out_shardings(src: Source):
    text = src.text
    if "jit" not in text:
        return
    if "shard" not in text and "device_put" not in text:
        return  # no sharding vocabulary: nothing can carry SHARD
    ma = _df.of(src)
    module_fa = ma.module_fa
    for site in ma.jit_sites():
        if site.out_shardings is not False:
            continue  # pinned, or a **kwargs splat decides at runtime
        sharded = False
        # (a) the traced body itself reads a sharded closure/global
        if site.analysis is not None and any(
            _df.SHARD in t for t in site.analysis.node_tags.values()
        ):
            sharded = True
        # (b) a module-local call site feeds the program a sharded operand
        if not sharded:
            names = _jit_bound_names(src, site)
            callers = []
            if isinstance(site.node, ast.Call):
                parent = src.parent(site.node)
                if isinstance(parent, ast.Call) and parent.func is site.node:
                    callers.append(parent)  # jax.jit(f)(args) immediately
            if names:
                for node in ast.walk(src.tree):
                    if (
                        isinstance(node, ast.Call)
                        and _dotted(node.func) in names
                    ):
                        callers.append(node)
            for call in callers:
                args = list(call.args) + [kw.value for kw in call.keywords]
                if any(_df.SHARD in module_fa.tags(a) for a in args):
                    sharded = True
                    break
        if sharded:
            yield _finding(
                src,
                "unpinned-out-shardings",
                site.node,
                "jit program flows a mesh-sharded value without "
                "out_shardings: GSPMD may gather the sharded slab onto one "
                "chip (round-12 lesson; see parallel/mesh_slab.py) -- pin "
                "the output shardings, or allow() stating why propagation "
                "from the operands is the intended layout",
            )


_POOL_STATE_FACTORIES = {"builder_for": "builder", "devcache_for": "devcache"}
_POOL_STATE_MUTATORS = {
    "submit", "submit_many", "remove", "remove_many", "lease", "lease_many",
    "unlease", "unlease_many", "set_nodes", "set_queues",
    "assemble_delta", "apply", "scatter_content", "prefetch_content",
    "invalidate_prefetch", "note_running_gang", "forget_running_gang",
}


def _pool_fn_stmts(fn) -> list:
    """The function's statements in document order, excluding nested defs
    (different scope, different dispatch windows)."""
    out: list = []

    def walk(stmts):
        for st in stmts:
            out.append(st)
            for field in ("body", "orelse", "finalbody"):
                inner = getattr(st, field, None)
                if inner and not isinstance(
                    st, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    walk(inner)
            for h in getattr(st, "handlers", ()) or ():
                walk(h.body)

    walk(fn.body)
    return out


_DISPATCH_OPENERS = ("dispatch_round_on_device", "dispatch_pool_rounds")


@rule(
    "pool-dispatch-mutation",
    "host-side mutation of a pool's builder/devcache between its round "
    "DISPATCH (dispatch_round_on_device, or the windowed "
    "dispatch_pool_rounds) and its FETCH (the finish call / the loop that "
    "consumes the finishes): the in-flight round's failover ground truth "
    "(bundle.materialize) closes over live builder state, so a mid-flight "
    "mutation makes a mesh/CPU re-run solve a DIFFERENT problem than the "
    "round it replaces -- the cross-pool zombie-write hazard class "
    "(round 17)",
    scope=under("armada_tpu/"),
)
def _pool_dispatch_mutation(src: Source):
    # Covers BOTH dispatch shapes: the solo dispatch_round_on_device handle
    # and the windowed dispatch_pool_rounds list-of-finishes (container
    # flow through `window.append` + inlining of nested-local-def calls
    # like flush_window, so the window list built in the enclosing scope
    # and dispatched inside the helper shares one value-flow state).
    text = src.text
    if all(op not in text for op in _DISPATCH_OPENERS):
        return
    _df.of(src)  # share the module's one dataflow pass (memoized per Source)
    fns = [
        n
        for n in ast.walk(src.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    seen_sites: set = set()
    for fn in fns:
        # value-flow per function: name -> frozenset of (kind, key) pool
        # sources (derived transitively from builder_for/devcache_for
        # calls, key = the normalized pool argument), plus the open
        # dispatch windows (finish handle name -> the sources its dispatch
        # call closed over).
        bindings: dict = {}
        open_dispatch: dict = {}
        local_defs = {
            n.name: n
            for n in ast.walk(fn)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n is not fn
        }
        findings: list = []

        def expr_sources(node) -> frozenset:
            out: set = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    out |= bindings.get(sub.id, frozenset())
            return frozenset(out)

        def step(st, inline_stack: frozenset) -> None:
            # (0) a For consuming an open window's finishes closes it (the
            # windowed fetch loop: `for e, fin in zip(entries, finishes)`),
            # and the loop targets inherit the iterated sources
            if isinstance(st, (ast.For, ast.AsyncFor)):
                iter_names = {
                    n.id for n in ast.walk(st.iter) if isinstance(n, ast.Name)
                }
                for h in [h for h in open_dispatch if h in iter_names]:
                    open_dispatch.pop(h, None)
                srcs = expr_sources(st.iter)
                for sub in ast.walk(st.target):
                    if isinstance(sub, ast.Name):
                        bindings[sub.id] = srcs
            # (1) a finish call closes its dispatch window (direct call,
            # `.finish()`, or an indexed handle `finishes[i]()`)
            for sub in ast.walk(st):
                if isinstance(sub, ast.Call):
                    name = None
                    if isinstance(sub.func, ast.Name):
                        name = sub.func.id
                    elif (
                        isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "finish"
                        and isinstance(sub.func.value, ast.Name)
                    ):
                        name = sub.func.value.id
                    elif isinstance(sub.func, ast.Subscript) and isinstance(
                        sub.func.value, ast.Name
                    ):
                        name = sub.func.value.id
                    if name in open_dispatch:
                        open_dispatch.pop(name, None)
            exposed = (
                frozenset().union(*open_dispatch.values())
                if open_dispatch
                else frozenset()
            )
            # (2) mutations of an in-flight pool's state
            if exposed:
                for sub in ast.walk(st):
                    if (
                        isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _POOL_STATE_MUTATORS
                        and expr_sources(sub.func.value) & exposed
                    ):
                        site = (sub.lineno, sub.col_offset)
                        if site not in seen_sites:
                            seen_sites.add(site)
                            findings.append(
                                _finding(
                                    src,
                                    "pool-dispatch-mutation",
                                    sub,
                                    "builder/devcache state of a DISPATCHED "
                                    "pool round mutated before its fetch: "
                                    "the failover ladder's materialize() "
                                    "would re-run a different problem -- "
                                    "commit mutations after the finish "
                                    "call, or route them through another "
                                    "pool's state",
                                )
                            )
                        break
            # (3) container flow: `window.append(entry)` merges the entry's
            # pool sources into the window binding (the windowed shape)
            for sub in ast.walk(st):
                if (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in ("append", "extend")
                    and isinstance(sub.func.value, ast.Name)
                    and sub.args
                ):
                    srcs = frozenset().union(
                        frozenset(), *(expr_sources(a) for a in sub.args)
                    )
                    if srcs:
                        key = sub.func.value.id
                        bindings[key] = bindings.get(key, frozenset()) | srcs
            # (4) binding propagation (rebinding clears)
            if isinstance(st, ast.Assign) and st.value is not None:
                srcs = frozenset()
                val = st.value
                if isinstance(val, ast.Call):
                    last = _dotted(val.func).rsplit(".", 1)[-1]
                    if last in _POOL_STATE_FACTORIES:
                        key = ast.dump(val.args[0]) if val.args else "<kw>"
                        srcs = frozenset(
                            {(_POOL_STATE_FACTORIES[last], key)}
                        )
                    elif last in _DISPATCH_OPENERS:
                        opened = expr_sources(val)
                        for tgt in st.targets:
                            if isinstance(tgt, ast.Name):
                                open_dispatch[tgt.id] = opened
                            elif (
                                isinstance(tgt, ast.Tuple)
                                and tgt.elts
                                and isinstance(tgt.elts[0], ast.Name)
                            ):
                                # `finishes, stacked, ... = dispatch_pool_
                                # rounds(specs, cfg)`: the handle list is
                                # the first element by API contract
                                open_dispatch[tgt.elts[0].id] = opened
                        srcs = frozenset()
                    else:
                        srcs = expr_sources(val)
                else:
                    srcs = expr_sources(val)
                if not (
                    isinstance(val, ast.Call)
                    and _dotted(val.func).rsplit(".", 1)[-1] in _DISPATCH_OPENERS
                ):
                    for tgt in st.targets:
                        for sub in ast.walk(tgt):
                            if isinstance(sub, ast.Name):
                                bindings[sub.id] = srcs
            # (5) inline calls to nested local defs with SHARED state: the
            # windowed flush helper dispatches/fetches over the enclosing
            # scope's window list
            for sub in ast.walk(st):
                if (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Name)
                    and sub.func.id in local_defs
                    and sub.func.id not in inline_stack
                ):
                    callee = local_defs[sub.func.id]
                    params = [a.arg for a in callee.args.args]
                    for p, a in zip(params, sub.args):
                        bindings[p] = expr_sources(a)
                    for cst in _pool_fn_stmts(callee):
                        step(cst, inline_stack | {sub.func.id})

        for st in _pool_fn_stmts(fn):
            step(st, frozenset())
        yield from findings


# -- v3 re-homing: value-flow provenance across helper/module boundaries ----
# The ingest rules below track their own domain tags (shard owners, shard
# indices, record fields).  When a binding's value is a call to a PROJECT
# helper (module-local or imported), dataflow.helper_flow_args tells us
# which argument expressions actually flow into the return, so the rules
# union their tags over THOSE instead of losing provenance (or smearing it
# over every name in the call).


def _flow_exprs(ma, val) -> Optional[list]:
    """Call-site argument expressions flowing into a project helper call's
    return, or None when `val` is not a resolvable helper call -- callers
    fall back to their conservative all-names union."""
    if not isinstance(val, ast.Call):
        return None
    return _df.helper_flow_args(ma, val)


def _helper_poll_arg(ma, call: ast.Call) -> Optional[ast.AST]:
    """For `raw = poll_shard(shard, n)` where the project helper's body
    polls off one of its own parameters (`s.consumer.poll()` /
    `s.poll_raw(...)`), the call-site argument expression standing for
    that parameter: the wrapped-poll shape keeps its shard provenance."""
    fname = _dotted(call.func)
    if not fname:
        return None
    target = ma.module_defs.get(fname)
    if target is None:
        ent = ma.imported_def(fname)
        if ent is None:
            return None
        _, target = ent
    params = [a.arg for a in target.args.args]
    owner_param = None
    for sub in ast.walk(target):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr in ("poll_raw", "_poll_raw", "poll")
        ):
            owner = sub.func.value
            if isinstance(owner, ast.Attribute) and owner.attr in (
                "consumer",
                "_consumer",
            ):
                owner = owner.value
            if isinstance(owner, ast.Name) and owner.id in params:
                owner_param = owner.id
                break
    if owner_param is None:
        return None
    pos = params.index(owner_param)
    if pos < len(call.args):
        return call.args[pos]
    for kw in call.keywords:
        if kw.arg == owner_param:
            return kw.value
    return None


def _is_row_maker(ma, call: ast.Call, ctors: tuple) -> bool:
    """True when `call` targets a project helper whose body constructs a
    DLQ row (DeadLetter/make_dead_letter): `row = build_row(rec, exc)`
    anchors as a row even though the ctor sits behind the helper."""
    fname = _dotted(call.func)
    if not fname:
        return False
    target = ma.module_defs.get(fname)
    if target is None:
        ent = ma.imported_def(fname)
        if ent is None:
            return False
        _, target = ent
    return any(
        isinstance(c, ast.Call) and _dotted(c.func).rsplit(".", 1)[-1] in ctors
        for c in ast.walk(target)
    )


@rule(
    "shard-foreign-cursor",
    "a shard's sink store carrying consumer_positions rows derived from "
    "ANOTHER shard's poll: each shard of the partition-parallel ingest "
    "plane owns a disjoint partition set and must commit ONLY its own "
    "cursor rows -- a foreign-cursor store acks partitions whose data "
    "lives in a different transaction, so a crash between the two stores "
    "silently skips that shard's batch on restart (round 18)",
    scope=under("armada_tpu/"),
)
def _shard_foreign_cursor(src: Source):
    # Value-flow per function: positions values are tagged with the shard
    # expression whose poll produced them (`X.poll_raw(...)` /
    # `X._poll_raw(...)` / `X.consumer.poll()` -> owner X); a store through
    # `Y.sink.store(..., next_positions=P)` is flagged when P carries
    # shard tags that do NOT include Y.  Untagged positions (dict
    # literals, parameters) stay clean -- provenance unknown is not a
    # violation, it is the inline single-shard shape.
    if "next_positions" not in src.text or ".store" not in src.text:
        return
    ma = _df.of(src)  # share the module's one dataflow pass (memoized per Source)

    def _owner_key(expr: ast.AST) -> Optional[str]:
        """The shard expression a poll/store hangs off: for
        `A.consumer.poll` / `A._consumer.poll` / `A.sink.store` the owner
        is A; for `X.poll_raw` it is X."""
        return ast.dump(expr, annotate_fields=False, include_attributes=False)

    for fn in (
        n
        for n in ast.walk(src.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ):
        bindings: dict = {}

        def expr_tags(node) -> frozenset:
            out: set = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    out |= bindings.get(sub.id, frozenset())
            return frozenset(out)

        for st in _pool_fn_stmts(fn):
            # (1) stores: receiver shard vs the positions' provenance
            for sub in ast.walk(st):
                if not (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in ("store", "store_plan")
                    and isinstance(sub.func.value, ast.Attribute)
                    and sub.func.value.attr in ("sink", "_sink")
                ):
                    continue
                receiver = _owner_key(sub.func.value.value)
                for kw in sub.keywords:
                    if kw.arg != "next_positions":
                        continue
                    tags = expr_tags(kw.value)
                    if tags and receiver not in tags:
                        yield _finding(
                            src,
                            "shard-foreign-cursor",
                            sub,
                            "next_positions derived from a different "
                            "shard's poll: cursor rows must commit in the "
                            "SAME transaction as their shard's data -- "
                            "ack through the shard that polled them",
                        )
            # (2) binding propagation: poll results carry their shard tag;
            # project-helper calls keep provenance across the boundary
            # (wrapped polls tag the call-site shard arg, transforms union
            # only the args that FLOW into the return)
            if isinstance(st, ast.Assign) and st.value is not None:
                tags: frozenset = frozenset()
                val = st.value
                if isinstance(val, ast.Call) and isinstance(
                    val.func, ast.Attribute
                ):
                    attr = val.func.attr
                    owner: Optional[ast.AST] = None
                    if attr in ("poll_raw", "_poll_raw", "poll"):
                        owner = val.func.value
                        if isinstance(owner, ast.Attribute) and owner.attr in (
                            "consumer",
                            "_consumer",
                        ):
                            owner = owner.value
                    if owner is not None:
                        tags = frozenset({_owner_key(owner)})
                    else:
                        tags = expr_tags(val)
                elif isinstance(val, ast.Call):
                    parg = _helper_poll_arg(ma, val)
                    if parg is not None:
                        tags = frozenset({_owner_key(parg)}) | expr_tags(parg)
                    else:
                        flow = _flow_exprs(ma, val)
                        if flow is None:
                            tags = expr_tags(val)
                        else:
                            tags = frozenset().union(
                                frozenset(), *(expr_tags(a) for a in flow)
                            )
                else:
                    tags = expr_tags(val)
                for tgt in st.targets:
                    for sub in ast.walk(tgt):
                        if isinstance(sub, ast.Name):
                            bindings[sub.id] = tags


@rule(
    "store-shard-foreign-write",
    "a store-shard write executed through ANOTHER shard's database handle: "
    "each store shard file/schema owns a disjoint partition set, so a batch "
    "written through a foreign handle lands rows in a file future ingestion "
    "never updates (and whose cursor fence commits elsewhere) -- route "
    "every write through the shard_sink/shard_store handle of the shard "
    "index that produced the payload (round 19)",
    scope=under("armada_tpu/"),
)
def _store_shard_foreign_write(src: Source):
    # Value-flow per function: a handle bound from `X.shard_sink(K, n)` /
    # `X.shard_store(K)` is tagged with its shard-index expression K; a
    # value bound from a subscript (per-shard batch/plan/position
    # collections, `plans[K]`) carries the index tag too.  A `.store` /
    # `.store_plan` / `.execute` through a tagged handle whose payload
    # carries ONLY different-index tags is flagged.  Untagged payloads
    # (parameters, literals) stay clean -- provenance unknown is not a
    # violation, it is the single-store shape.
    if "shard_sink" not in src.text and "shard_store" not in src.text:
        return
    ma = _df.of(src)  # share the module's one dataflow pass (memoized per Source)

    def _key(expr: ast.AST) -> str:
        return ast.dump(expr, annotate_fields=False, include_attributes=False)

    def _handle_index(call: ast.AST) -> Optional[str]:
        if (
            isinstance(call, ast.Call)
            and isinstance(call.func, ast.Attribute)
            and call.func.attr in ("shard_sink", "shard_store")
            and call.args
        ):
            return _key(call.args[0])
        return None

    for fn in (
        n
        for n in ast.walk(src.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ):
        handles: dict = {}  # name -> frozenset of shard-index keys
        data: dict = {}  # name -> frozenset of shard-index keys

        def data_tags(node) -> frozenset:
            out: set = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    out |= data.get(sub.id, frozenset())
            return frozenset(out)

        def _own_exprs(st):
            # the statement's OWN expressions: nested statements get their
            # own document-order turn (checking them here would run the
            # write check before their preceding bindings land)
            for field, value in ast.iter_fields(st):
                if field in ("body", "orelse", "finalbody", "handlers"):
                    continue
                for node in value if isinstance(value, list) else [value]:
                    if isinstance(node, ast.AST) and not isinstance(
                        node, ast.stmt
                    ):
                        yield from ast.walk(node)

        for st in _pool_fn_stmts(fn):
            # (1) writes: handle shard index vs the payload's provenance
            for sub in _own_exprs(st):
                if not (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in ("store", "store_plan", "execute")
                ):
                    continue
                recv = sub.func.value
                hidx = _handle_index(recv)
                if hidx is not None:
                    rtags = frozenset({hidx})
                elif isinstance(recv, ast.Name):
                    rtags = handles.get(recv.id, frozenset())
                else:
                    rtags = frozenset()
                if not rtags:
                    continue
                ptags: set = set()
                for arg in list(sub.args) + [kw.value for kw in sub.keywords]:
                    ptags |= data_tags(arg)
                if ptags and rtags.isdisjoint(ptags):
                    yield _finding(
                        src,
                        "store-shard-foreign-write",
                        sub,
                        "payload derived from a different shard index than "
                        "this handle's shard_sink/shard_store index: the "
                        "rows land in a file that shard's ingestion and "
                        "cursor fence never touch -- write through the "
                        "producing shard's own handle",
                    )
            # (2) binding propagation: handles carry their index expression,
            # subscripted per-shard collections carry theirs
            if isinstance(st, ast.Assign) and st.value is not None:
                val = st.value
                hidx = _handle_index(val)
                if hidx is not None:
                    for tgt in st.targets:
                        for s2 in ast.walk(tgt):
                            if isinstance(s2, ast.Name):
                                handles[s2.id] = frozenset({hidx})
                    continue
                if isinstance(val, ast.Subscript):
                    tags = frozenset({_key(val.slice)})
                elif isinstance(val, ast.Call):
                    # project-helper transforms keep the index tag across
                    # the boundary: union over the args that FLOW into the
                    # return, with a flowing per-shard subscript
                    # (`render(plans[k])`) contributing its index key
                    flow = _flow_exprs(ma, val)
                    if flow is None:
                        tags = data_tags(val)
                    else:
                        out: set = set()
                        for a in flow:
                            if isinstance(a, ast.Subscript):
                                out.add(_key(a.slice))
                            out |= data_tags(a)
                        tags = frozenset(out)
                else:
                    tags = data_tags(val)
                for tgt in st.targets:
                    for s2 in ast.walk(tgt):
                        if isinstance(s2, ast.Name):
                            data[s2.id] = tags


@rule(
    "dlq-cursor-same-txn",
    "a dead-letter quarantine whose cursor advance rides a DIFFERENT "
    "record's positions (or none at all): the DLQ row and the consumer "
    "cursor must commit in the SAME shard store transaction -- a crash "
    "between them either loses the poison record for good (cursor past "
    "it, no DLQ row) or re-quarantines it forever (row committed, cursor "
    "behind; round 21)",
    scope=under("armada_tpu/"),
)
def _dlq_cursor_same_txn(src: Source):
    # Value-flow per function: a value bound from a DeadLetter(...) /
    # make_dead_letter(...) construction is a ROW and carries the name
    # tags of the record fields it was built from; the next_positions
    # argument of a `store_dead_letters` call must share at least one tag
    # with the quarantined rows (the same record's partition/offset).
    # Disjoint provenance = the cursor advances for a different record
    # than the one being quarantined.  A rows-carrying call with NO
    # next_positions (or an empty dict literal) splits the quarantine and
    # the cursor advance into two transactions.  Untraced rows
    # (parameters -- the pure-delegation shape) stay clean: provenance
    # unknown is not a violation.
    if "store_dead_letters" not in src.text:
        return
    ma = _df.of(src)  # share the module's one dataflow pass (memoized per Source)

    _ROW_CTORS = ("DeadLetter", "make_dead_letter")

    def _own_exprs(st):
        for field, value in ast.iter_fields(st):
            if field in ("body", "orelse", "finalbody", "handlers"):
                continue
            for node in value if isinstance(value, list) else [value]:
                if isinstance(node, ast.AST) and not isinstance(node, ast.stmt):
                    yield from ast.walk(node)

    for fn in (
        n
        for n in ast.walk(src.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ):
        bindings: dict = {}  # name -> frozenset of provenance tags
        rowtags: dict = {}  # name -> frozenset (only names bound from a row ctor)

        def tags(node) -> frozenset:
            out: set = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    out |= bindings.get(sub.id, frozenset({sub.id}))
            return frozenset(out)

        def row_tags(node) -> frozenset:
            out: set = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    out |= rowtags.get(sub.id, frozenset())
            return frozenset(out)

        for st in _pool_fn_stmts(fn):
            # (1) quarantine calls: rows provenance vs cursor provenance
            for sub in _own_exprs(st):
                if not (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == "store_dead_letters"
                    and sub.args
                ):
                    continue
                rt = row_tags(sub.args[0])
                if not rt:
                    continue  # untraced rows: the delegation shape
                np_kw = next(
                    (k for k in sub.keywords if k.arg == "next_positions"),
                    None,
                )
                if np_kw is None or (
                    isinstance(np_kw.value, ast.Dict) and not np_kw.value.keys
                ):
                    yield _finding(
                        src,
                        "dlq-cursor-same-txn",
                        sub,
                        "quarantine without a cursor advance in the same "
                        "store transaction: pass the record's "
                        "next_positions to store_dead_letters so the DLQ "
                        "row and the cursor commit atomically",
                    )
                    continue
                pt = tags(np_kw.value)
                if pt and rt.isdisjoint(pt):
                    yield _finding(
                        src,
                        "dlq-cursor-same-txn",
                        sub,
                        "next_positions derived from a different record "
                        "than the quarantined rows: the cursor must "
                        "advance past exactly the record whose DLQ row "
                        "commits in this transaction",
                    )
            # (2) binding propagation: row constructions carry their
            # record-field tags (a project helper whose body calls the
            # ctor anchors as a row too -- v3 boundary crossing, with the
            # tag set narrowed to the args that FLOW into the return);
            # everything else unions its names' tags
            if isinstance(st, ast.Assign) and st.value is not None:
                val = st.value
                is_row = any(
                    isinstance(c, ast.Call)
                    and _dotted(c.func).rsplit(".", 1)[-1] in _ROW_CTORS
                    for c in ast.walk(val)
                )
                helper_row = None
                if not is_row:
                    helper_row = next(
                        (
                            c
                            for c in ast.walk(val)
                            if isinstance(c, ast.Call)
                            and _is_row_maker(ma, c, _ROW_CTORS)
                        ),
                        None,
                    )
                    is_row = helper_row is not None
                t = tags(val)
                if helper_row is not None:
                    flow = _flow_exprs(ma, helper_row)
                    if flow is not None:
                        t = frozenset().union(
                            frozenset(), *(tags(a) for a in flow)
                        )
                rtag = t if is_row else row_tags(val)
                for tgt in st.targets:
                    for s2 in ast.walk(tgt):
                        if isinstance(s2, ast.Name):
                            bindings[s2.id] = t
                            if rtag:
                                rowtags[s2.id] = rtag
                            else:
                                rowtags.pop(s2.id, None)


@rule(
    "vectorized-accumulator-ordering",
    "a reduction-produced value (jnp.sum/cumsum/dot -- any association-"
    "sensitive reduce) feeding an ordering comparison against a carry "
    "accumulator inside a kernel loop body: f32 addition is non-"
    "associative, so a vectorized sum disagrees with the sequential "
    "path's one-at-a-time association and flips cap/near-tie decisions "
    "(round 15: accumulators feeding ordering comparisons MUST add "
    "committed picks one at a time in rank order)",
    scope=_KERNEL_DF,
)
def _vectorized_accumulator_ordering(src: Source):
    if "while_loop" not in src.text and "fori_loop" not in src.text:
        return
    ma = _df.of(src)
    seen: set = set()
    _ORD = (ast.Lt, ast.LtE, ast.Gt, ast.GtE)
    for fa in _loop_body_analyses(ma):
        fn = fa.fn
        root = fn if not isinstance(fn, ast.Lambda) else fn.body
        for cmp_node in ast.walk(root):
            if not (
                isinstance(cmp_node, ast.Compare)
                and any(isinstance(op, _ORD) for op in cmp_node.ops)
            ):
                continue
            for node in ast.walk(cmp_node):
                if not (
                    isinstance(node, ast.BinOp)
                    and isinstance(node.op, (ast.Add, ast.Sub))
                ):
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:
                    continue
                lt, rt = fa.tags(node.left), fa.tags(node.right)
                for red, acc in ((lt, rt), (rt, lt)):
                    if (
                        _df.REDUCED in red
                        and _df.CARRY in acc
                        and _df.REDUCED not in acc
                    ):
                        seen.add(key)
                        yield _finding(
                            src,
                            "vectorized-accumulator-ordering",
                            node,
                            "a reduction-produced value is added to a "
                            "carry accumulator inside an ordering "
                            "comparison: f32 addition is non-associative, "
                            "so the vectorized sum can flip near-ties "
                            "against the sequential oracle -- accumulate "
                            "committed picks one at a time in rank order "
                            "(CLAUDE.md round-15 exactness lesson), or "
                            "allow with a proof the operands are exact "
                            "(integral resolution units)",
                        )
                        break


# The scheduling-class identity fields (core/keys.class_signature): a
# hashable combining >= _SIG_MIN of these reads off ONE object outside
# core/keys is a second hand-rolled signature -- the r5 divergence
# (IndexError into the compat matrix) in the making.
_SIG_FIELDS = {
    "resources",
    "node_selector",
    "tolerations",
    "priority_class",
    "priority",
    "node_type_scores",
}
_SIG_MIN = 3


def _sig_helper_reads(ma, call: ast.Call) -> frozenset:
    """(root, field) pairs a project-helper call reads off its arguments:
    `selector_items(job)` whose body touches `j.node_selector` yields
    ("job", "node_selector") -- field-read provenance across the helper
    boundary."""
    fname = _dotted(call.func)
    if not fname:
        return frozenset()
    target = ma.module_defs.get(fname)
    if target is None:
        ent = ma.imported_def(fname)
        if ent is None:
            return frozenset()
        _, target = ent
    params = [a.arg for a in target.args.args]
    arg_root: dict = {}
    for p, a in zip(params, call.args):
        if isinstance(a, ast.Name):
            arg_root[p] = a.id
    for kw in call.keywords:
        if kw.arg in params and isinstance(kw.value, ast.Name):
            arg_root[kw.arg] = kw.value.id
    out: set = set()
    for sub in ast.walk(target):
        if (
            isinstance(sub, ast.Attribute)
            and sub.attr in _SIG_FIELDS
            and isinstance(sub.value, ast.Name)
            and sub.value.id in arg_root
        ):
            out.add((arg_root[sub.value.id], sub.attr))
    return frozenset(out)


@rule(
    "class-signature-home",
    "a hashable tuple built from the scheduling-class field-read set "
    "(resources/node_selector/tolerations/priority_class/priority/"
    "node_type_scores) outside core/keys: scheduling-class identity lives "
    "in ONE place (core/keys.class_signature) -- a second hand-rolled "
    "signature diverged on the excluded node-id label and crashed "
    "validation with an IndexError into the compat matrix (round 5)",
    scope=lambda p: p.startswith("armada_tpu/")
    and p != "armada_tpu/core/keys.py",
)
def _class_signature_home(src: Source):
    hits = sum(1 for f in _SIG_FIELDS if f in src.text)
    if hits < _SIG_MIN:
        return
    ma = _df.of(src)

    def direct_reads(node) -> frozenset:
        out: set = set()
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Attribute)
                and sub.attr in _SIG_FIELDS
                and isinstance(sub.value, ast.Name)
            ):
                out.add((sub.value.id, sub.attr))
            elif isinstance(sub, ast.Call):
                out |= _sig_helper_reads(ma, sub)
        return frozenset(out)

    seen: set = set()
    for fn in (
        n
        for n in ast.walk(src.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ):
        bindings: dict = {}  # name -> frozenset of (root, field) pairs

        def expr_reads(node) -> frozenset:
            out = set(direct_reads(node))
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    out |= bindings.get(sub.id, frozenset())
            return frozenset(out)

        for st in _pool_fn_stmts(fn):
            # (1) hashable tuples combining the class field-read set
            # (subscript INDEX tuples are array indexing, not identity)
            idx_tuples = {
                id(s.slice)
                for s in ast.walk(st)
                if isinstance(s, ast.Subscript)
            }
            for sub in ast.walk(st):
                if not (
                    isinstance(sub, ast.Tuple)
                    and isinstance(getattr(sub, "ctx", None), ast.Load)
                    and len(sub.elts) >= 2
                    and id(sub) not in idx_tuples
                    and not any(
                        isinstance(e, ast.Slice) for e in sub.elts
                    )
                ):
                    continue
                key = (sub.lineno, sub.col_offset)
                if key in seen:
                    continue
                per_root: dict = {}
                for r, f in expr_reads(sub):
                    per_root.setdefault(r, set()).add(f)
                if any(len(fs) >= _SIG_MIN for fs in per_root.values()):
                    seen.add(key)
                    yield _finding(
                        src,
                        "class-signature-home",
                        sub,
                        "tuple combines >= 3 scheduling-class identity "
                        "fields of one object: a second hand-rolled class "
                        "signature WILL diverge from the gang-split/"
                        "SubmitChecker identity -- call core/keys."
                        "class_signature (or build the tuple there)",
                    )
                    break
            # (2) binding propagation (rebinding clears)
            if isinstance(st, ast.Assign) and st.value is not None:
                reads = expr_reads(st.value)
                for tgt in st.targets:
                    for s2 in ast.walk(tgt):
                        if isinstance(s2, ast.Name):
                            bindings[s2.id] = reads


_THREAD_SPAWNERS = {"threading.Thread", "Thread", "_thread.start_new_thread"}


@rule(
    "unmade-lock",
    "a raw threading.Lock()/RLock() constructed in a module that spawns "
    "threads: locks in threaded code route through tsan.make_lock (named) "
    "so the ARMADA_TSAN race harness sees the ordering -- a raw lock is "
    "invisible to it",
    scope=lambda p: p.startswith("armada_tpu/")
    and p != "armada_tpu/analysis/tsan.py",
)
def _unmade_lock(src: Source):
    if "threading" not in src.text:
        return
    spawns = False
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name in _THREAD_SPAWNERS or name.rsplit(".", 1)[-1] == (
                "ThreadPoolExecutor"
            ):
                spawns = True
                break
    if not spawns:
        return  # single-threaded module: a plain Lock has nothing to race
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in (
            "threading.Lock",
            "threading.RLock",
            "Lock",
            "RLock",
        ):
            yield _finding(
                src,
                "unmade-lock",
                node,
                "raw lock in a thread-spawning module: construct it with "
                "tsan.make_lock('<name>') so the dynamic race harness "
                "(ARMADA_TSAN=1) records its ordering; plain-Lock "
                "semantics when disarmed, ~one attribute check armed",
            )


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------

def lint_source(text: str, relpath: str) -> list[Finding]:
    """Lint one buffer as if it lived at `relpath` (rule scoping applies).
    Returns findings sorted by (line, col, rule); suppressed findings are
    dropped, reasonless allows surface as `allow-missing-reason`."""
    try:
        src = Source(text, relpath)
    except SyntaxError as e:
        return [
            Finding(
                "syntax-error",
                relpath.replace(os.sep, "/"),
                e.lineno or 0,
                e.offset or 0,
                f"file does not parse: {e.msg}",
            )
        ]
    return _lint_src(src)


def _lint_src(src: Source) -> list[Finding]:
    out: list[Finding] = []
    for line, rules in src.reasonless_allows:
        out.append(
            Finding(
                "allow-missing-reason",
                src.relpath,
                line,
                0,
                f"allow({rules}) without a reason: write "
                "`# lint: allow(rule) -- why this site is exempt`",
            )
        )
    for r in RULES:
        if not r.scope(src.relpath):
            continue
        for f in r.check(src):
            node = ast.AST()  # suppression check wants a node-like span
            node.lineno = f.line
            node.end_lineno = f.end_line or f.line
            if not src.suppressed(f.rule, node):
                out.append(f)
    out.sort(key=lambda f: (f.line, f.col, f.rule))
    return out


def lint_file(path: str, root: str) -> list[Finding]:
    rel = os.path.relpath(path, root)
    with open(path, "r", encoding="utf-8") as fh:
        return lint_source(fh.read(), rel)


def lint_file_deps(path: str, root: str) -> tuple[list[Finding], dict]:
    """(findings, {relpath: content-hash}) for one file: the hash map
    covers the file itself plus every project module its dataflow
    analysis consulted (transitively via ModuleAnalysis.deps) -- the
    invalidation key for `tools/lint.py --cache`.  A cached entry is
    valid iff every hash in the map still matches."""
    rel = os.path.relpath(path, root)
    relp = rel.replace(os.sep, "/")
    with open(path, "r", encoding="utf-8") as fh:
        text = fh.read()
    deps = {relp: _df.content_hash(path)}
    try:
        src = Source(text, rel)
    except SyntaxError:
        return lint_source(text, rel), deps
    findings = _lint_src(src)
    ma = getattr(src, "_dataflow", None)
    if ma is not None:
        deps.update(_df.dep_hashes(ma))
        deps[relp] = _df.content_hash(path)
    return findings, deps


# Walk exclusions: generated protobuf modules (not authored here), fixture
# files (deliberate true positives), payload/test data, VCS internals, and
# the git-ignored second copy of the tree a builder unpacks for the chip
# tool (.chipcheck -- its fixture copies are true positives too).
EXCLUDE_DIRS = {
    ".git",
    ".chipcheck",
    "__pycache__",
    ".pytest_cache",
    "node_modules",
    "testdata",
}
EXCLUDE_REL = ("tests/lint_fixtures",)
EXCLUDE_FILE_PATTERNS = ("_pb2.py", "_pb2_grpc.py")


def iter_python_files(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        rel_dir = os.path.relpath(dirpath, root).replace(os.sep, "/")
        dirnames[:] = sorted(
            d
            for d in dirnames
            if d not in EXCLUDE_DIRS
            and not (rel_dir + "/" + d if rel_dir != "." else d).startswith(
                EXCLUDE_REL
            )
        )
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            if any(name.endswith(pat) for pat in EXCLUDE_FILE_PATTERNS):
                continue
            yield os.path.join(dirpath, name)


def lint_tree(root: str) -> tuple[int, list[Finding]]:
    """(files scanned, findings) over every authored .py under `root`."""
    findings: list[Finding] = []
    n = 0
    for path in iter_python_files(root):
        n += 1
        findings.extend(lint_file(path, root))
    return n, findings


def suppression_census(root: str) -> list[tuple[str, int, str, str]]:
    """Every reasoned `# lint: allow(...)` in the tree as (relpath, line,
    rule, reason) rows -- the raw material for `tools/lint.py --stats`, so
    stale allows stay visible instead of accumulating silently."""
    rows: list[tuple[str, int, str, str]] = []
    for path in iter_python_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, "r", encoding="utf-8") as fh:
            _, _, records = _parse_suppressions(fh.read())
        for line, rules, reason in records:
            for r in sorted(rules):
                rows.append((rel, line, r, reason))
    return rows
