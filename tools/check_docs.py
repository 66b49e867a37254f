"""Docs lint: documented commands must not rot.

Extracts fenced ``bash`` code blocks from README.md, docs/architecture.md,
DESIGN.md and docs/observability.md, finds every ``python ...`` invocation,
and checks that

* the referenced script / module file exists in the repo;
* for argparse-based benchmark scripts, every ``--flag`` used in the
  documented command appears in the script's ``--help`` output (the help
  text is fetched once per script via a subprocess);
* ``--trace`` / ``--policy`` values name real entries in the
  ``repro.sched`` registries, and ``--mesh`` values parse as ``rows,cols``;
* relative markdown links in the scanned files resolve to real paths.

Run:  PYTHONPATH=src python tools/check_docs.py
(the CI ``docs`` job; ``tests/test_docs.py`` runs the same checks in
tier-1).  Exits non-zero listing every violation.
"""
from __future__ import annotations

import re
import subprocess
import sys
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parent.parent

DOC_FILES = ("README.md", "docs/architecture.md", "DESIGN.md",
             "docs/observability.md")

# scripts whose documented flags are validated against their --help output
# (examples/ scripts take no arguments and are only checked for existence)
ARGPARSE_SCRIPTS = ("benchmarks/cluster_sim.py", "benchmarks/mapping_engine.py",
                    "benchmarks/serving_sim.py", "benchmarks/fleet_sim.py",
                    "benchmarks/chaos_sim.py", "tools/trace_report.py",
                    "chip_smoke.py")

# non-repo executables we do not try to resolve
SKIP_MODULES = ("pytest", "pip", "doctest", "venv")

_FENCE_RE = re.compile(r"```(?:bash|sh|console)\n(.*?)```", re.DOTALL)
_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)#]+)\)")


def extract_commands(text: str) -> List[str]:
    """Command lines (continuations joined, comments stripped) from every
    fenced bash block."""
    out: List[str] = []
    for block in _FENCE_RE.findall(text):
        pending = ""
        for raw in block.splitlines():
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            line = line.split("#", 1)[0].rstrip()
            if line.endswith("\\"):
                pending += line[:-1] + " "
                continue
            out.append(" ".join((pending + line).split()))
            pending = ""
        if pending:
            out.append(pending.strip())
    return [c for c in out if "python" in c.split()[0] or " python" in c
            or c.startswith("python")]


def parse_python_command(cmd: str):
    """(target, flags, values) of one documented ``python`` invocation.

    ``target`` is a script path or ``-m <module>``; ``flags`` are the
    ``--options`` used; ``values`` maps a flag to its value when given as
    the next token or ``--flag=value``.
    """
    tokens = cmd.split()
    # drop env assignments (PYTHONPATH=src) and the interpreter
    while tokens and ("=" in tokens[0] and not tokens[0].startswith("-")):
        tokens.pop(0)
    if not tokens or not tokens[0].startswith("python"):
        return None
    tokens.pop(0)
    if not tokens:
        return None
    if tokens[0] == "-m":
        target = f"-m {tokens[1]}"
        rest = tokens[2:]
    else:
        target = tokens[0]
        rest = tokens[1:]
    flags: List[str] = []
    values: Dict[str, str] = {}
    i = 0
    while i < len(rest):
        tok = rest[i]
        if tok.startswith("--"):
            if "=" in tok:
                flag, val = tok.split("=", 1)
                flags.append(flag)
                values[flag] = val
            else:
                flags.append(tok)
                if i + 1 < len(rest) and not rest[i + 1].startswith("-"):
                    values[tok] = rest[i + 1]
                    i += 1
        i += 1
    return target, flags, values


def module_path(module: str) -> Path:
    p = ROOT / (module.replace(".", "/") + ".py")
    if p.exists():
        return p
    return ROOT / module.replace(".", "/") / "__main__.py"


class DocChecker:
    def __init__(self) -> None:
        self.errors: List[str] = []
        self._help_cache: Dict[str, str] = {}
        self._registries = None

    # -- helpers -----------------------------------------------------------
    def _help_text(self, script: str) -> str:
        text = self._help_cache.get(script)
        if text is None:
            import os
            env = dict(os.environ)
            env["PYTHONPATH"] = str(ROOT / "src") + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
                else "")
            proc = subprocess.run(
                [sys.executable, script, "--help"], cwd=ROOT, env=env,
                capture_output=True, text=True, timeout=120)
            text = proc.stdout + proc.stderr
            if proc.returncode != 0:
                self.errors.append(f"{script} --help exited "
                                   f"{proc.returncode}: {text[-300:]}")
            self._help_cache[script] = text
        return text

    def _registry(self):
        if self._registries is None:
            sys.path.insert(0, str(ROOT / "src"))
            from repro.sched.policy import POLICIES
            from repro.sched.traces import TRACES
            self._registries = (set(TRACES), set(POLICIES))
        return self._registries

    # -- checks ------------------------------------------------------------
    def check_command(self, doc: str, cmd: str) -> None:
        parsed = parse_python_command(cmd)
        if parsed is None:
            return
        target, flags, values = parsed
        if target.startswith("-m "):
            module = target[3:]
            if module.split(".")[0] in SKIP_MODULES:
                return
            if not module_path(module).exists():
                self.errors.append(
                    f"{doc}: `{cmd}` references missing module {module}")
            return
        script = target
        if not (ROOT / script).exists():
            self.errors.append(
                f"{doc}: `{cmd}` references missing file {script}")
            return
        if script not in ARGPARSE_SCRIPTS:
            return
        help_text = self._help_text(script)
        for flag in flags:
            if flag not in help_text:
                self.errors.append(
                    f"{doc}: `{cmd}` uses {flag}, absent from "
                    f"{script} --help")
        traces, policies = self._registry()
        if "--trace" in values and values["--trace"] not in traces:
            self.errors.append(
                f"{doc}: `{cmd}` names unknown trace "
                f"{values['--trace']!r} (have {sorted(traces)})")
        if "--policy" in values:
            for p in values["--policy"].split(","):
                if p and p not in policies:
                    self.errors.append(
                        f"{doc}: `{cmd}` names unknown policy {p!r}")
        if "--mesh" in values:
            parts = values["--mesh"].split(",")
            if len(parts) != 2 or not all(x.isdigit() for x in parts):
                self.errors.append(
                    f"{doc}: `{cmd}` has malformed --mesh "
                    f"{values['--mesh']!r} (want rows,cols)")

    def check_links(self, doc: str, text: str) -> None:
        base = (ROOT / doc).parent
        for link in _LINK_RE.findall(text):
            link = link.strip()
            if link.startswith(("http://", "https://", "mailto:")):
                continue
            if not (base / link).exists() and not (ROOT / link).exists():
                self.errors.append(f"{doc}: broken link -> {link}")

    def run(self) -> int:
        for doc in DOC_FILES:
            path = ROOT / doc
            if not path.exists():
                self.errors.append(f"missing doc file: {doc}")
                continue
            text = path.read_text()
            self.check_links(doc, text)
            for cmd in extract_commands(text):
                self.check_command(doc, cmd)
        if self.errors:
            print(f"check_docs: {len(self.errors)} problem(s)")
            for e in self.errors:
                print(f"  - {e}")
            return 1
        print(f"check_docs: OK ({', '.join(DOC_FILES)})")
        return 0


def main(argv=None) -> int:
    return DocChecker().run()


if __name__ == "__main__":
    raise SystemExit(main())
