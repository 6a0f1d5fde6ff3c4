"""traced-purity: no host calls inside jit/pjit/shard_map-lowered code.

Provenance: every engine program lowers through ``parallel/dispatch.lower``
(or ``jax.jit`` / ``jax.shard_map`` directly — sim/engine.py, PR 7), and
a host call inside a traced body is a classic silent bug: ``time.time()``
burns ONE timestamp into the compiled graph forever, ``np.random`` draws
once at trace time and replays the same "random" numbers every call,
``print`` fires at trace time only (then never again), ``datetime.now``
likewise. jax.debug.print / jax.random are the traced-safe counterparts.

Scope: per module — functions (a) decorated with ``jax.jit`` /
``partial(jax.jit, ...)``, or (b) passed by NAME as the first argument to
``jax.jit`` / ``jax.shard_map`` / ``dispatch.lower`` /
``jit_under_mesh`` / ``pallas_call`` (or as a row of a table whose loop
variable is: ``facts._row_handles``), plus every ``def`` nested inside
them. No interprocedural analysis: a helper called from a traced body is
only scanned if it is itself lowered — the rule catches the direct form.
"""

from __future__ import annotations

from fedml_tpu.analysis.core import Finding, Project, Rule
from fedml_tpu.analysis.facts import FileFacts


class TracedPurityRule(Rule):
    name = "traced-purity"
    description = ("banned host calls (time.time, np.random.*, print, "
                   "datetime.now) inside jit/pjit/shard_map-lowered "
                   "functions; banned-module-calls entries ban a pattern "
                   "module-wide (e.g. np.random.* anywhere under "
                   "fedml_tpu/population/ — replay determinism)")

    def __init__(self, config):
        self.config = config
        self.banned = tuple(config.banned_traced_calls)
        # "<path-prefix>:<pattern>" module-wide bans (config.py)
        self.module_banned: list[tuple[str, str]] = []
        for entry in getattr(config, "banned_module_calls", ()):
            prefix, sep, pattern = entry.partition(":")
            if not sep or not prefix or not pattern:
                raise ValueError(
                    f"banned-module-calls entry {entry!r}: expected "
                    "'<path-prefix>:<call-pattern>'"
                )
            self.module_banned.append((prefix, pattern))

    @staticmethod
    def _match(dotted: str, pattern: str) -> bool:
        if pattern.endswith(".*"):
            return dotted.startswith(pattern[:-1])
        return dotted == pattern

    def _banned_match(self, dotted: str) -> str | None:
        for pattern in self.banned:
            if self._match(dotted, pattern):
                return pattern
        return None

    def check(self, file: FileFacts, project: Project) -> list[Finding]:
        traced_names = {name for name, _via in file.lowered_names}
        findings: list[Finding] = []

        def scan(root_func, owner: str) -> None:
            for func in project.subtree(file, root_func):
                for call_idx in func.calls:
                    call = file.calls[call_idx]
                    if call.dotted is None:
                        continue
                    pattern = self._banned_match(call.dotted)
                    if pattern is None:
                        continue
                    findings.append(Finding(
                        self.name, file.path, call.line, call.col,
                        f"host call {call.dotted}() inside traced function "
                        f"`{owner}` (matches banned pattern {pattern!r}) — "
                        "traced programs must be pure: the value burns "
                        "into the compiled graph at trace time",
                    ))

        for func in file.functions:
            if func.kind == "lambda":
                if func.lowered_via is not None:
                    scan(func, f"<lambda via {func.lowered_via}>")
            elif func.jit_decorated or func.name in traced_names:
                scan(func, func.name)

        # module-wide bans: in files under a configured path prefix, the
        # banned pattern is illegal at ANY scope, not just traced bodies —
        # the population subsystem's replay-determinism contract (every
        # draw through its seeded rng, population/prng.py)
        module_patterns = [
            pat for prefix, pat in self.module_banned
            if file.path.replace("\\", "/").startswith(prefix)
        ]
        if module_patterns:
            seen = {(f.line, f.col) for f in findings}
            for call in file.calls:
                if call.dotted is None:
                    continue
                for pattern in module_patterns:
                    if not self._match(call.dotted, pattern):
                        continue
                    if (call.line, call.col) in seen:
                        break
                    findings.append(Finding(
                        self.name, file.path, call.line, call.col,
                        f"call {call.dotted}() matches pattern {pattern!r} "
                        f"banned module-wide under this path "
                        "(banned-module-calls) — draws here must flow "
                        "through the subsystem's seeded rng so trace "
                        "replay stays deterministic",
                    ))
                    break
        return findings
