"""exception-status: no silent swallows; one status vocabulary.

The runtime's error-handling contract, two halves:

1. **Exceptions.** No bare ``except:`` anywhere in the package (it
   eats ``KeyboardInterrupt``/``SystemExit`` and wedges shutdown). In
   ``runtime/``, a broad handler (``except Exception`` /
   ``BaseException``) must carry a justification — a comment on the
   except clause or immediately inside the handler explaining WHY
   catching everything is right there (the repo's ``# noqa: BLE001 —
   reason`` convention, or a staticcheck pragma). An unexplained broad
   catch is where real faults go to disappear; ~50 existing sites all
   carry their reasons, and this pass keeps it that way.

2. **Status vocabulary.** The HTTP/gRPC surfaces answer ONLY from the
   registered status sets (deploy/README's fault matrix is written
   against them): HTTP {200, 204, 400, 404, 405, 411, 413, 415, 429,
   500, 503} and gRPC {OK, INVALID_ARGUMENT, NOT_FOUND,
   RESOURCE_EXHAUSTED, UNAVAILABLE, INTERNAL, UNIMPLEMENTED}. A
   handler inventing a new code (or typoing one — 419, ``EXHAUSTED``)
   silently breaks every client retry policy written against the
   documented set. Checked over literal ``send_response``/
   ``send_error`` arguments, literal ``status =`` assignments in the
   server modules, and ``StatusCode.X`` attribute references.
"""

from __future__ import annotations

import ast

import re

from ..core import PRAGMA_RE, Repo, SourceFile, Violation

# Content-free comment markers that do NOT count as a written reason:
# a justification must say WHY, not merely wave off another linter.
_DIRECTIVE_RE = re.compile(
    r"noqa(:\s*[A-Z0-9, ]+)?"
    r"|type:\s*ignore(\[[^\]]*\])?"
    r"|pragma:\s*no\s*cover"
    r"|(?i:todo|fixme|xxx)\b[:\s]*"
)

PASS_ID = "exception-status"
DESCRIPTION = (
    "no bare except; broad excepts in runtime/ carry reasons; "
    "HTTP/gRPC handlers answer only from the registered status sets"
)

HTTP_STATUSES = {200, 204, 400, 404, 405, 411, 413, 415, 429, 500, 503}
GRPC_STATUSES = {
    "OK", "INVALID_ARGUMENT", "NOT_FOUND", "RESOURCE_EXHAUSTED",
    "UNAVAILABLE", "INTERNAL", "UNIMPLEMENTED", "DEADLINE_EXCEEDED",
}
# Server modules whose integer status literals are HTTP answer codes.
HTTP_SERVER_MODULES = (
    "runtime/otlp.py", "runtime/query.py", "telemetry/metrics.py",
)
BROAD = {"Exception", "BaseException"}


def _has_justification(src: SourceFile, handler: ast.ExceptHandler) -> bool:
    """A comment on the except line, between it and the first
    statement, or on the first statement's line counts as the reason
    (the repo's `# noqa: BLE001 — why` convention lives there).

    Real comments only (tokenizer, so a ``#`` inside a string literal
    doesn't count); a ``staticcheck: ok[...]`` pragma is NOT a
    free-text reason — the violation must still be emitted so the
    suppression machinery consumes it (marks it used, enforces its
    reason) instead of reporting the pragma as stale — and neither is
    a content-free lint marker (bare ``# noqa``, ``# type: ignore``,
    ``# TODO``): some explanatory text must remain after stripping
    those."""
    first = handler.body[0].lineno if handler.body else handler.lineno
    for ln in range(handler.lineno, min(first, len(src.lines)) + 1):
        comment = src.comments.get(ln)
        if not comment:
            continue
        text = PRAGMA_RE.sub("", comment)
        text = _DIRECTIVE_RE.sub("", text)
        if re.search(r"\w", text):
            return True
    return False


def _status_ints(node: ast.AST) -> list[tuple[int, int]]:
    """(value, line) integer literals inside a status expression —
    resolves the `503 if degraded else 200` conditional shape."""
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, int) \
                and not isinstance(sub.value, bool):
            out.append((sub.value, getattr(sub, "lineno", 0)))
    return out


def run(repo: Repo) -> list[Violation]:
    out: list[Violation] = []
    if repo.package is None:
        return out
    runtime_prefix = f"{repo.package}/runtime/"
    http_modules = {f"{repo.package}/{m}" for m in HTTP_SERVER_MODULES}
    for rel in repo.iter_py(repo.package):
        src = repo.source(rel)
        if src is None or src.tree is None:
            continue
        in_runtime = rel.startswith(runtime_prefix)
        for node in ast.walk(src.tree):
            # -- exceptions --------------------------------------------
            if isinstance(node, ast.ExceptHandler):
                if node.type is None:
                    out.append(Violation(
                        PASS_ID, rel, node.lineno,
                        "bare `except:` swallows KeyboardInterrupt/"
                        "SystemExit — catch Exception (with a reason) "
                        "or the specific type",
                    ))
                    continue
                if not in_runtime:
                    continue
                names = [
                    n.id for n in ast.walk(node.type)
                    if isinstance(n, ast.Name)
                ]
                if not any(n in BROAD for n in names):
                    continue
                if not _has_justification(src, node):
                    out.append(Violation(
                        PASS_ID, rel, node.lineno,
                        "broad `except Exception` with no stated reason: "
                        "narrow it, or justify the catch-all in a "
                        "comment on the clause (`# noqa: BLE001 — why`)",
                    ))
                continue
            # -- status vocabulary ---------------------------------------
            if rel in http_modules and isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("send_response", "send_error") and \
                    node.args:
                for value, line in _status_ints(node.args[0]):
                    if value not in HTTP_STATUSES:
                        out.append(Violation(
                            PASS_ID, rel, line or node.lineno,
                            f"HTTP status {value} is outside the "
                            f"registered vocabulary {sorted(HTTP_STATUSES)} "
                            "— the fault matrix and client retry "
                            "policies are written against that set",
                        ))
            elif rel in http_modules and isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "status":
                        for value, line in _status_ints(node.value):
                            if 100 <= value <= 599 and \
                                    value not in HTTP_STATUSES:
                                out.append(Violation(
                                    PASS_ID, rel, line or node.lineno,
                                    f"HTTP status {value} assigned but "
                                    "outside the registered vocabulary "
                                    f"{sorted(HTTP_STATUSES)}",
                                ))
            elif in_runtime and isinstance(node, ast.Attribute) and \
                    isinstance(node.value, ast.Attribute) and \
                    node.value.attr == "StatusCode" and \
                    node.attr.isupper():
                if node.attr not in GRPC_STATUSES:
                    out.append(Violation(
                        PASS_ID, rel, node.lineno,
                        f"gRPC StatusCode.{node.attr} is outside the "
                        f"registered vocabulary {sorted(GRPC_STATUSES)}",
                    ))
    return out
