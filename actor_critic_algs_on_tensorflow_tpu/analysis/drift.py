"""DRIFT*: config-knob / CLI / README / metric-registry agreement.

The repo's operational surface is three hand-maintained lists that
must agree: the ``ImpalaConfig`` dataclass (what exists), the CLI's
``--set`` coercion (what is reachable), the README knob tables (what
is documented), and ``utils/metric_names.py`` (what the log stream
emits). Rules:

  DRIFT001  a config field (``ImpalaConfig``, or an off-policy
            trainer config — DDPG/TD3/SAC) whose default is not
            coercible by ``utils.config._coerce`` — unreachable via
            ``--set``
  DRIFT002  a ``transport_*``/``pipeline_*``/``serve_*``/``device_*``/
            ``shard*`` metric key used in source but missing from the
            ``METRIC_NAMES`` registry
  DRIFT003  a registry key no source file emits or reads (orphan —
            the registry rotted ahead of the code)
  DRIFT004  a registry collision: duplicate declaration, or a metric
            name identical to a config-knob name (one string, two
            meanings, in one log stream)
  DRIFT005  an ``ImpalaConfig`` field with no README knob-table row —
            and, for the off-policy configs, a ``per_*``/``replay_*``
            field without one: the distributed replay tier's
            operational knobs are README-documented by contract
            (core off-policy training hyperparameters are preset-
            owned and exempt)

Metric *uses* are collected statically: dict-literal keys, subscript
keys (read or write), ``.get("...")`` first args, ``TimeSplit``
prefix + ``.add("...")`` / ``.span("...")`` names, and ``LatencyStats.summary(prefix)``
expansions — with names resolved through the ``metric_names``
constants and f-string interpolations rendered as ``*`` wildcards.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

from actor_critic_algs_on_tensorflow_tpu.analysis.core import (
    Finding,
    checker,
    fold_str,
    func_name,
    parse_file,
    rel,
)

# shard keys: shard0_*/shard*_* dynamic, shard_* statics, and the
# bare "shards" count — but NOT a lone "shard" (a common kwarg name).
# tenant keys follow the same shape: tenant0_*/tenant*_* dynamic and
# tenant_* statics — but NOT "tenant"/"tenants" (ubiquitous kwargs).
_FAMILY_RE = re.compile(
    r"^(transport_|pipeline_|serve_|device_|replay_pipeline_|replay_"
    r"|elastic_|autoscaler_|delivery_|promo_"
    r"|shard[0-9*]|shard_|shards$"
    r"|tenant[0-9*]|tenant_)"
    r"[A-Za-z0-9_*]*$"
)
# TimeSplit's default prefix. utils/metrics.py defaults to
# metric_names.PIPELINE; the checker resolves the live value from the
# registry's constants at check time (importing metric_names here
# would drag in the jax-heavy utils package __init__) — this literal
# is only the last-resort fallback when the registry is unreadable.
_TIMESPLIT_DEFAULT = "pipeline_"
_SUMMARY_SUFFIXES = ("count", "mean_ms", "p50_ms", "p99_ms", "max_ms")

_CONFIG_REL = "actor_critic_algs_on_tensorflow_tpu/algos/impala.py"
# Off-policy trainer configs: every field must be --set-coercible
# (DRIFT001); the distributed replay tier's operational knobs
# (``per_*``/``replay_*``, and the elastic fleet's
# ``elastic_*``/``autoscaler_*``) additionally need README rows
# (DRIFT005).
_OFFPOLICY_CONFIGS = {
    "actor_critic_algs_on_tensorflow_tpu/algos/ddpg.py": "DDPGConfig",
    "actor_critic_algs_on_tensorflow_tpu/algos/td3.py": "TD3Config",
    "actor_critic_algs_on_tensorflow_tpu/algos/sac.py": "SACConfig",
}
_OFFPOLICY_DOC_RE = re.compile(
    r"^(per_|replay_|elastic_|autoscaler_)"
)
_REGISTRY_REL = "actor_critic_algs_on_tensorflow_tpu/utils/metric_names.py"
# Files whose family-prefixed strings are metric uses. Tests are
# excluded (they assert against literals on purpose); the analysis
# package only talks ABOUT the keys.
_SCAN_SKIP_PARTS = ("tests", "analysis")


def _is_family(key: str) -> bool:
    return bool(_FAMILY_RE.match(key)) and not key.startswith("shard_map")


def metric_name_consts(registry: Path) -> Dict[str, str]:
    """String constants assigned at metric_names module level
    (``TRANSPORT = "transport_"`` ...) for name resolution."""
    out: Dict[str, str] = {}
    try:
        tree = parse_file(registry)
    except (OSError, SyntaxError):
        return out
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            val = fold_str(node.value, out)
            if val is not None:
                out[node.targets[0].id] = val
    return out


def declared_names(registry: Path) -> Tuple[Dict[str, int], List[Tuple[str, int]]]:
    """``METRIC_NAMES`` dict-literal keys with lines, plus duplicate
    declarations as (key, line) pairs."""
    consts = metric_name_consts(registry)
    declared: Dict[str, int] = {}
    dupes: List[Tuple[str, int]] = []
    try:
        tree = parse_file(registry)
    except (OSError, SyntaxError):
        return declared, dupes
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for tgt in targets:
            if (
                isinstance(tgt, ast.Name)
                and tgt.id == "METRIC_NAMES"
                and isinstance(node.value, ast.Dict)
            ):
                for k in node.value.keys:
                    key = fold_str(k, consts) if k is not None else None
                    if key is None:
                        continue
                    if key in declared:
                        dupes.append((key, k.lineno))
                    else:
                        declared[key] = k.lineno
    return declared, dupes


def collect_metric_uses(
    root: Path, files: Sequence[Path], consts: Dict[str, str]
) -> Dict[str, Tuple[str, int]]:
    """Family-prefixed metric keys used anywhere in scanned source:
    ``{key_or_pattern: (file, line)}`` (first use wins)."""
    uses: Dict[str, Tuple[str, int]] = {}
    default_prefix = consts.get("PIPELINE", _TIMESPLIT_DEFAULT)

    def record(key, path, line):
        if key and _is_family(key) and key not in uses:
            uses[key] = (path, line)

    def timesplit_prefix(call: ast.Call) -> str:
        pref = default_prefix
        if call.args:
            folded = fold_str(call.args[0], consts)
            if folded is not None:
                pref = folded
        for kw in call.keywords:
            if kw.arg == "prefix":
                folded = fold_str(kw.value, consts)
                if folded is not None:
                    pref = folded
        return pref

    for p in files:
        if p.suffix != ".py":
            continue
        rp = rel(root, p)
        parts = rp.split("/")
        if any(part in _SCAN_SKIP_PARTS for part in parts):
            continue
        if rp == _REGISTRY_REL:
            continue
        try:
            tree = parse_file(p)
        except SyntaxError:
            continue
        # TimeSplit prefixes bound in this module: var/attr name ->
        # set of prefixes (ambiguous bindings fall back to the union).
        prefix_bindings: Dict[str, set] = {}
        module_prefixes: set = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and func_name(node.func) == (
                "TimeSplit"
            ):
                module_prefixes.add(timesplit_prefix(node))
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ) and func_name(node.value.func) == "TimeSplit":
                pref = timesplit_prefix(node.value)
                for tgt in node.targets:
                    name = func_name(tgt)
                    if name:
                        prefix_bindings.setdefault(name, set()).add(pref)

        for node in ast.walk(tree):
            # Dict-literal keys.
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if k is not None:
                        record(fold_str(k, consts), rp, k.lineno)
            # Subscript keys, read or write: m["transport_x"].
            elif isinstance(node, ast.Subscript):
                record(fold_str(node.slice, consts), rp, node.lineno)
            elif isinstance(node, ast.Call):
                leaf = func_name(node.func)
                # .get("key", default) reads.
                if leaf == "get" and node.args:
                    record(fold_str(node.args[0], consts), rp,
                           node.lineno)
                # TimeSplit .add("name", seconds) and .span("name")
                # -> prefix + name. (A bare ``profiling.span`` has no
                # counter behind it: only a receiver this module bound
                # to a TimeSplit makes a ``span`` a metric use.)
                elif leaf in ("add", "span") and node.args and isinstance(
                    node.func, ast.Attribute
                ):
                    name = fold_str(node.args[0], consts)
                    if name is not None and re.fullmatch(
                        r"[a-z0-9_]+", name
                    ):
                        recv = func_name(node.func.value)
                        prefixes = prefix_bindings.get(recv)
                        if prefixes is None and leaf == "span":
                            prefixes = set()
                        elif prefixes is None or len(prefixes) > 1:
                            prefixes = module_prefixes or set()
                        for pref in prefixes:
                            record(pref + name, rp, node.lineno)
                # LatencyStats .summary(prefix) -> 5 fixed suffixes.
                elif leaf == "summary":
                    pref = None
                    if node.args:
                        pref = fold_str(node.args[0], consts)
                    for kw in node.keywords:
                        if kw.arg == "prefix":
                            pref = fold_str(kw.value, consts)
                    if pref:
                        for suffix in _SUMMARY_SUFFIXES:
                            record(pref + suffix, rp, node.lineno)
    return uses


def _matches(a: str, b: str) -> bool:
    return a == b or fnmatch.fnmatch(a, b) or fnmatch.fnmatch(b, a)


def config_fields(
    config_path: Path, class_name: str = "ImpalaConfig"
) -> Dict[str, Tuple[int, ast.AST]]:
    """``class_name``'s fields: ``{name: (line, default_node)}``."""
    out: Dict[str, Tuple[int, ast.AST]] = {}
    try:
        tree = parse_file(config_path)
    except (OSError, SyntaxError):
        return out
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name
                ):
                    out[stmt.target.id] = (stmt.lineno, stmt.value)
    return out


def _coercible(default: ast.AST | None) -> bool:
    """Mirrors ``utils.config._coerce``: bool/int/float/str/None
    defaults and tuples of those are CLI-reachable."""
    if default is None:
        return False
    if isinstance(default, ast.Constant):
        return isinstance(
            default.value, (bool, int, float, str, type(None))
        )
    if isinstance(default, ast.Tuple):
        return all(
            isinstance(e, ast.Constant)
            and isinstance(e.value, (bool, int, float, str))
            for e in default.elts
        )
    if isinstance(default, ast.UnaryOp) and isinstance(
        default.operand, ast.Constant
    ):
        return True
    return False


def readme_knob_rows(readme: Path) -> set:
    """Backticked names in README table rows (``| `knob` | ...``)."""
    out = set()
    if not readme.exists():
        return out
    for line in readme.read_text(encoding="utf-8").splitlines():
        if line.lstrip().startswith("|"):
            out.update(re.findall(r"`([A-Za-z0-9_.]+)`", line))
    return out


@checker(
    "drift",
    rules=("DRIFT001", "DRIFT002", "DRIFT003", "DRIFT004", "DRIFT005"),
    anchors=(
        _CONFIG_REL,
        _REGISTRY_REL,
        "README.md",
        "actor_critic_algs_on_tensorflow_tpu/**/*.py",
        "scripts/*.py",
        "bench.py",
        "scaling_bench.py",
    ),
)
def check(root: Path, files: Sequence[Path]) -> List[Finding]:
    """Knob/metric/doc drift: config-CLI-README agreement and the
    metric-name registry's two-way orphan check."""
    findings: List[Finding] = []
    config_path = next(
        (p for p in files if rel(root, p) == _CONFIG_REL), None
    )
    registry = next(
        (p for p in files if rel(root, p) == _REGISTRY_REL), None
    )
    readme = root / "README.md"

    rows = readme_knob_rows(readme)
    fields: Dict[str, Tuple[int, ast.AST]] = {}
    if config_path is not None:
        fields = config_fields(config_path)
        for name, (line, default) in sorted(fields.items()):
            if not _coercible(default):
                findings.append(Finding(
                    "DRIFT001", _CONFIG_REL, line,
                    f"ImpalaConfig.{name} has a default that --set "
                    f"cannot coerce (utils.config._coerce handles "
                    f"bool/int/float/str/None/tuple literals)",
                    hint="give the field a coercible default or add "
                         "a coercion branch to utils.config._coerce",
                ))
            if name not in rows:
                findings.append(Finding(
                    "DRIFT005", _CONFIG_REL, line,
                    f"ImpalaConfig.{name} has no README knob-table "
                    f"row",
                    hint="add a `| name | default | effect |` row to "
                         "the README config reference",
                ))
    for cfg_rel, cls in sorted(_OFFPOLICY_CONFIGS.items()):
        cfg_file = next(
            (p for p in files if rel(root, p) == cfg_rel), None
        )
        if cfg_file is None:
            continue
        op_fields = config_fields(cfg_file, cls)
        for name, (line, default) in sorted(op_fields.items()):
            if not _coercible(default):
                findings.append(Finding(
                    "DRIFT001", cfg_rel, line,
                    f"{cls}.{name} has a default that --set cannot "
                    f"coerce (utils.config._coerce handles "
                    f"bool/int/float/str/None/tuple literals)",
                    hint="give the field a coercible default or add "
                         "a coercion branch to utils.config._coerce",
                ))
            if _OFFPOLICY_DOC_RE.match(name) and name not in rows:
                findings.append(Finding(
                    "DRIFT005", cfg_rel, line,
                    f"{cls}.{name} is a distributed replay-tier "
                    f"knob with no README knob-table row",
                    hint="add a `| name | default | effect |` row to "
                         "the README replay-tier section",
                ))
            # Replay-tier knobs join the metric/knob collision
            # surface: their names interleave with replay_* metrics
            # in one log stream.
            if _OFFPOLICY_DOC_RE.match(name) and name not in fields:
                fields[name] = (line, default)

    if registry is None:
        return findings
    consts = metric_name_consts(registry)
    declared, dupes = declared_names(registry)
    uses = collect_metric_uses(root, files, consts)

    for key, line in dupes:
        findings.append(Finding(
            "DRIFT004", _REGISTRY_REL, line,
            f"metric name {key!r} declared more than once",
            hint="keep one declaration per key",
        ))
    for key, line in sorted(declared.items()):
        if key in fields:
            findings.append(Finding(
                "DRIFT004", _REGISTRY_REL, line,
                f"metric name {key!r} collides with an ImpalaConfig "
                f"knob of the same name — one string, two meanings",
                hint="rename the metric (or the knob); the log "
                     "stream interleaves both",
            ))
    for key, (path, line) in sorted(uses.items()):
        if not any(_matches(key, d) for d in declared):
            findings.append(Finding(
                "DRIFT002", path, line,
                f"metric key {key!r} is not declared in "
                f"utils/metric_names.py METRIC_NAMES",
                hint="declare it (with provenance) in the registry — "
                     "or fix the typo'd key",
            ))
    for key, line in sorted(declared.items()):
        if not any(_matches(key, u) for u in uses):
            findings.append(Finding(
                "DRIFT003", _REGISTRY_REL, line,
                f"registry metric {key!r} is never emitted or read "
                f"by any scanned source file (orphan)",
                hint="delete the stale registry entry",
            ))
    return findings
