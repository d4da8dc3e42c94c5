"""docs-check: keep README/ARCHITECTURE/benchmarks docs honest.

Checks, for README.md, docs/ARCHITECTURE.md and benchmarks/README.md:

  1. every ```bash code-block command is real: `make <target>` targets exist
     in the Makefile, `python -m <module>` modules resolve (with src/ on the
     path), `python <script>` files exist;
  2. every ```python code block actually runs (executed with src/ on
     sys.path — keep doc snippets small and fast);
  3. every backticked flag-ish token (`span_*`, `lmbr_*`, `mla_*`, ...)
     names a real `repro.flags.FLAGS` key, and every backticked variant
     component (e.g. `spanjax`, `peelreference+lmbrcache0`) parses through
     `repro.flags.set_variant`;
  4. every relative markdown link points at an existing file.

Exit code 0 = docs are consistent with the code.  Run via `make docs-check`
(part of `make ci`).
"""

from __future__ import annotations

import importlib.util
import re
import shlex
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOCS = ["README.md", "docs/ARCHITECTURE.md", "benchmarks/README.md"]

sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))  # for `benchmarks.*` modules

from repro import flags  # noqa: E402

FLAG_PREFIXES = ("span_", "lmbr_", "mla_", "moe_", "accum_", "sp_",
                 "router_", "drift_", "scale_", "placement_", "durability_",
                 "node_", "migration_", "obs_", "health_")
# flag-prefixed identifiers that are NOT flags (kernel / bench row names,
# serving counters, profile columns, API parameters)
NON_FLAGS = {"span_gain", "span_gain_calibration", "span_gain_ref",
             "span_gain_tile", "span_round_calibration", "drift_fires",
             "node_weights", "node_cost", "placement_seconds",
             "placement_stats", "durability_copies", "durability_eps=0",
             "placement_s", "placement_applications", "span_ratio",
             "span_regret",
             "migration_copies", "migration_drops", "migration_ticks",
             "migration_done", "migration_transfer_gb",
             "migration_wasted_gb", "migration_max_inflight_gb",
             # metric / trace-event series names (repro.obs), not flags
             "router_microbatch_seconds", "router_partition_load",
             "router_plan_swaps_total", "router_served_queries_total",
             "router_microbatches_total", "migration_transferred",
             "migration_wasted", "migration_inflight",
             "migration_transferred_total", "migration_wasted_total",
             "migration_copies_total", "migration_drops_total",
             "drift_fires_total", "drift_refits_total", "lmbr_moves",
             "lmbr_peel_items", "lmbr_peel_classes",
             "health_alerts_fired_total", "health_alerts_resolved_total",
             "health_alerts_active"}
# backticked tokens that should parse as --variant specs
VARIANT_RE = re.compile(
    r"^(baseline|mla_decomp|sp2?|accum\d+|cf[\d.]+|spanth\d+|"
    r"span(auto|numpy|jax|pallas|interpret)|spanroundth\d+|"
    r"spanround(auto|numpy|device)|"
    r"peel(vector|reference|device|pallas|interpret)|"
    r"lmbrcache[01]|lmbrepoch(item|partition)|"
    r"routerbal[01]|routermb\d+|routereps[\d.]+|"
    r"driftw\d+|driftth[\d.]+|shards\d+|scalew\d+|brepair\d+|"
    r"migbw[\d.]+|migconc\d+|mighead[\d.]+|"
    r"obshealth[01]|obs(off|counters|trace)|obssnap\d+|"
    r"healthw\d+|healthhyst\d+|healthspan[\d.]+|healthp99[\d.]+|"
    r"healthdeg[\d.]+|healthskew[\d.]+|healthbacklog[\d.]+|healthz[\d.]+|"
    r"energy|durab[\d.e+-]+|nodecost[\d.]+|routercost[01])"
    r"(\+.+)?$"
)


def fenced_blocks(text: str):
    """Yield (language, body) for every fenced code block."""
    for m in re.finditer(r"```(\w*)\n(.*?)```", text, re.S):
        yield m.group(1), m.group(2)


def check_bash_line(line: str, errors: list[str], ctx: str):
    line = line.strip()
    if not line or line.startswith("#"):
        return
    try:
        toks = shlex.split(line)
    except ValueError:
        errors.append(f"{ctx}: unparseable command {line!r}")
        return
    while toks and re.match(r"^[A-Z_][A-Z0-9_]*=", toks[0]):
        toks = toks[1:]  # strip env-var prefixes like PYTHONPATH=src
    if not toks:
        return
    cmd = toks[0]
    if cmd == "make":
        makefile = (REPO / "Makefile").read_text()
        targets = set(re.findall(r"^([\w-]+):", makefile, re.M))
        for t in toks[1:]:
            if not t.startswith("-") and t not in targets:
                errors.append(f"{ctx}: make target {t!r} not in Makefile")
    elif cmd == "python":
        if len(toks) > 2 and toks[1] == "-m":
            mod = toks[2]
            if importlib.util.find_spec(mod) is None:
                errors.append(f"{ctx}: module {mod!r} does not resolve")
        elif len(toks) > 1 and toks[1].endswith(".py"):
            if not (REPO / toks[1]).exists():
                errors.append(f"{ctx}: script {toks[1]!r} not found")
    # other commands (git, pip, ...) are not emitted by our docs; ignore


def check_python_block(body: str, errors: list[str], ctx: str):
    env = {"__name__": "__docs_check__"}
    try:
        exec(compile(body, ctx, "exec"), env)
    except Exception as exc:  # noqa: BLE001 - report, don't crash the checker
        errors.append(f"{ctx}: python snippet failed: {type(exc).__name__}: {exc}")


def check_inline_tokens(text: str, errors: list[str], ctx: str):
    for tok in re.findall(r"`([^`\n]+)`", text):
        t = tok.strip().strip('"')
        if (re.fullmatch(r"[a-z][a-z0-9_]*", t) and t.startswith(FLAG_PREFIXES)
                and t not in NON_FLAGS):
            if t not in flags.FLAGS and not any(
                k.startswith(t) for k in flags.FLAGS
            ):
                errors.append(f"{ctx}: flag name `{t}` not in repro.flags.FLAGS")
        elif re.fullmatch(r"[a-z0-9_.+]+", t) and "+" in t:
            if VARIANT_RE.match(t):
                try:
                    flags.set_variant(t)
                except ValueError as exc:
                    errors.append(f"{ctx}: variant `{t}` rejected: {exc}")
                finally:
                    flags.reset()


def check_links(text: str, errors: list[str], doc: Path):
    for target in re.findall(r"\]\(([^)#]+?)\)", text):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        if not (doc.parent / target).exists() and not (REPO / target).exists():
            errors.append(f"{doc.name}: broken link -> {target}")


def main() -> int:
    errors: list[str] = []
    for rel in DOCS:
        doc = REPO / rel
        if not doc.exists():
            errors.append(f"missing doc: {rel}")
            continue
        text = doc.read_text()
        check_inline_tokens(text, errors, rel)
        check_links(text, errors, doc)
        for lang, body in fenced_blocks(text):
            if lang in ("bash", "sh", "shell"):
                for line in body.splitlines():
                    check_bash_line(line, errors, rel)
            elif lang == "python":
                check_python_block(body, errors, rel)
    # the tier-1 verify line in README must match ROADMAP's contract
    roadmap = (REPO / "ROADMAP.md").read_text()
    m = re.search(r"\*\*Tier-1 verify:\*\* `([^`]+)`", roadmap)
    if m and m.group(1).split("python ")[-1] not in (REPO / "README.md").read_text():
        errors.append("README quickstart does not mention the tier-1 verify command")
    if errors:
        print("docs-check: FAILED")
        for e in errors:
            print(f"  - {e}")
        return 1
    print("docs-check: OK (commands, snippets, flags, links)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
