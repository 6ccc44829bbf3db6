"""Rule family SC3 — the three-way metrics contract.

``production_stack_tpu/obs/metric_registry.py`` is the single source of
truth for every ``tpu:``/``tpu_router:`` family (SURVEY §4: the stats
plane is the backbone — scraper, dashboard, HPA rule and fake engine all
key off these names, and a silent rename desyncs them without any test
failing).  stackcheck cross-checks FOUR surfaces against it, in both
directions:

  emit sites    string literals in production_stack_tpu/** (fake engine
                excluded — it is a mirror, not an emitter)
  fake engine   testing/fake_engine.py must mirror every engine family
                flagged ``fake_engine`` (vocabulary constants and the
                EngineObs histogram render path are expanded)
  dashboard     observability/tpu-dashboard.json panel exprs
  docs          the docs/observability.md tables

SC301  emitted family missing from the registry (orphan emit)
SC302  registry family with no emit site (dead entry / rename drift)
SC303  engine family flagged fake_engine not mirrored by the fake
SC304  family flagged dashboard absent from every panel expr
SC305  dashboard expr references a family the registry doesn't know
SC306  family flagged docs absent from docs/observability.md
SC307  docs reference a family the registry doesn't know

prometheus_client quirk handled here: a ``Counter("x")`` is EXPOSED as
``x_total`` — the registry stores exposition names, and emit-site
scanning lifts literals declared inside ``Counter(...)`` accordingly.
"""

from __future__ import annotations

import ast
import json
import re
from pathlib import Path
from typing import Any, Dict, List, Set, Tuple, cast

from tools.stackcheck import config as C
from tools.stackcheck.core import SourceFile, Violation

FAMILY_RE = re.compile(r"\btpu(?:_router)?:[a-z0-9_]+\b")
HIST_SUFFIXES = ("_bucket", "_sum", "_count")
# Docs prose writes families in shell-brace shorthand
# (tpu:step_{schedule,dispatch}_seconds) and glob shorthand
# (tpu:step_*_seconds); expand the former, drop the latter.
_BRACE_RE = re.compile(r"\{([a-z0-9_,]+)\}")


def _prose_families(text: str) -> Set[str]:
    """Family names mentioned in prose/markdown: brace templates are
    expanded, glob templates (name immediately followed by ``*``/``<``)
    are ignored rather than matched as a truncated family."""
    names: Set[str] = set()
    for line in text.splitlines():
        for m in _BRACE_RE.finditer(line):
            if "," not in m.group(1):
                continue  # {server} is a label selector, not alternatives
            prefix = line[: m.start()]
            suffix = line[m.end():]
            pm = re.search(r"tpu(?:_router)?:[a-z0-9_]*_$", prefix)
            sm = re.match(r"[a-z0-9_]*", suffix)
            if pm:
                for alt in m.group(1).split(","):
                    names.add(pm.group(0) + alt + (sm.group(0) if sm else ""))
        for m in FAMILY_RE.finditer(line):
            nxt = line[m.end(): m.end() + 1]
            if nxt in ("*", "<") or m.group(0).endswith("_"):
                continue
            names.add(m.group(0))
    return names


def parse_registry(path: Path) -> Dict[str, Dict[str, object]]:
    """AST-parse the REGISTRY literal (never imports the package)."""
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "REGISTRY":
                    return cast(
                        Dict[str, Dict[str, object]],
                        ast.literal_eval(node.value),
                    )
    raise ValueError(f"no REGISTRY assignment found in {path}")


def _vocabulary_constants(path: Path) -> Tuple[Dict[str, str], Dict[str, Set[str]]]:
    """vocabulary.py NAME = "tpu:..." constants and NAME = {..} dicts
    (dict name -> set of family values)."""
    consts: Dict[str, str] = {}
    dicts: Dict[str, Set[str]] = {}
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not isinstance(tgt, ast.Name):
            continue
        try:
            value = ast.literal_eval(node.value)
        except (ValueError, SyntaxError):
            continue
        if isinstance(value, str) and FAMILY_RE.fullmatch(value):
            consts[tgt.id] = value
        elif isinstance(value, dict):
            fams = {
                v for v in value.values()
                if isinstance(v, str) and FAMILY_RE.fullmatch(v)
            }
            if fams:
                dicts[tgt.id] = fams
    return consts, dicts


def _is_docstring_const(parents: Dict[int, ast.AST], node: ast.Constant) -> bool:
    parent = parents.get(id(node))
    if not isinstance(parent, ast.Expr):
        return False
    gp = parents.get(id(parent))
    return isinstance(
        gp, (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)
    ) and gp.body and gp.body[0] is parent


def collect_emitted(sources: List[SourceFile],
                    skip_rels: Set[str]) -> Dict[str, Tuple[str, int]]:
    """Exposition family -> (file, line) for every emit-site literal.
    Literals inside prometheus_client Counter(...) calls are lifted to
    their ``_total`` exposition name; docstrings are ignored (prose)."""
    out: Dict[str, Tuple[str, int]] = {}
    for src in sources:
        if src.rel in skip_rels:
            continue
        parents: Dict[int, ast.AST] = {}
        counter_literals: Set[int] = set()
        for node in ast.walk(src.tree):
            for child in ast.iter_child_nodes(node):
                parents[id(child)] = node
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "Counter"
                and node.args
                and isinstance(node.args[0], ast.Constant)
            ):
                counter_literals.add(id(node.args[0]))
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Constant) or not isinstance(node.value, str):
                continue
            if _is_docstring_const(parents, node):
                continue
            for fam in FAMILY_RE.findall(node.value):
                if fam != node.value:
                    # Partial mention inside prose/comment-ish strings
                    # (format strings, error text): not an emit site.
                    continue
                name = fam
                if id(node) in counter_literals and not name.endswith("_total"):
                    name += "_total"
                out.setdefault(name, (src.rel, node.lineno))
    return out


def _normalize(name: str, registry: Dict[str, Dict[str, object]]) -> str:
    """Strip histogram exposition suffixes when the base is a registered
    histogram family."""
    if name in registry:
        return name
    for sfx in HIST_SUFFIXES:
        if name.endswith(sfx):
            base = name[: -len(sfx)]
            if registry.get(base, {}).get("kind") == "histogram":
                return base
    return name


def _dashboard_families(path: Path) -> Dict[str, str]:
    """family-name-as-written -> panel title, from every panel expr."""
    data = json.loads(path.read_text())
    out: Dict[str, str] = {}

    def walk_panels(panels: List[Dict[str, Any]]) -> None:
        for p in panels:
            title = p.get("title", "?")
            for t in p.get("targets", []):
                for fam in FAMILY_RE.findall(t.get("expr", "")):
                    out.setdefault(fam, title)
            if "panels" in p:
                walk_panels(p["panels"])

    walk_panels(data.get("panels", []))
    return out


def check_metrics(sources: List[SourceFile], cfg: C.Config) -> List[Violation]:
    out: List[Violation] = []
    reg_path = cfg.resolve(cfg.registry_path)
    if reg_path is None or not reg_path.exists():
        return [Violation(
            rule="SC302", file=cfg.registry_path or "<missing>", line=1,
            qualname="metric_registry",
            message="metric registry module missing", detail="missing",
        )]
    registry = parse_registry(reg_path)
    reg_rel = cfg.registry_path
    fake_rel = cfg.fake_engine_path

    emitted = collect_emitted(
        sources, skip_rels={reg_rel, fake_rel} if fake_rel else {reg_rel}
    )

    # SC301 / SC302 — emit sites vs registry.
    for fam, (file, line) in sorted(emitted.items()):
        if _normalize(fam, registry) not in registry:
            out.append(Violation(
                rule="SC301", file=file, line=line, qualname="metrics",
                message=(
                    f"metric family `{fam}` is emitted but absent from "
                    f"{reg_rel} (add it to REGISTRY with kind/layer/mirrors)"
                ),
                detail=fam,
            ))
    for fam, meta in sorted(registry.items()):
        source_name = meta.get("source_name", fam)
        if fam not in emitted and source_name not in emitted:
            out.append(Violation(
                rule="SC302", file=reg_rel, line=1, qualname="metrics",
                message=(
                    f"registry family `{fam}` has no emit site in the "
                    "package (renamed or removed without updating the "
                    "registry?)"
                ),
                detail=fam,
            ))

    # SC303 — fake-engine mirror.
    fake_path = cfg.resolve(cfg.fake_engine_path)
    vocab_path = cfg.resolve(cfg.vocabulary_path)
    if fake_path is not None and fake_path.exists():
        mirrored: Set[str] = set()
        fake_text = fake_path.read_text()
        mirrored.update(
            f for f in FAMILY_RE.findall(fake_text)
        )
        if vocab_path is not None and vocab_path.exists():
            consts, dicts = _vocabulary_constants(vocab_path)
            for cname, fam in consts.items():
                if re.search(rf"\b{re.escape(cname)}\b", fake_text):
                    mirrored.add(fam)
            for dname, fams in dicts.items():
                if re.search(rf"\b{re.escape(dname)}\b", fake_text):
                    mirrored.update(fams)
            # EngineObs.render_metrics() renders every histogram family
            # in the vocabulary dicts and the step thread's stall counter
            # — using it IS the mirror.
            if "render_metrics" in fake_text or "EngineObs" in fake_text:
                for dname in ("TPU_REQUEST_HISTOGRAMS", "TPU_STEP_HISTOGRAMS",
                              "TPU_KV_HISTOGRAMS"):
                    mirrored.update(dicts.get(dname, set()))
                if "TPU_STEP_STALL" in consts:
                    mirrored.add(consts["TPU_STEP_STALL"])
        for fam, meta in sorted(registry.items()):
            if meta.get("layer") != "engine":
                continue
            if "fake_engine" not in meta.get("mirrors", ()):
                continue
            if fam not in mirrored and meta.get("source_name", fam) not in mirrored:
                out.append(Violation(
                    rule="SC303", file=cfg.fake_engine_path, line=1,
                    qualname="metrics",
                    message=(
                        f"engine family `{fam}` is not mirrored by the "
                        "fake engine (router/CI tests exercise the "
                        "contract through it)"
                    ),
                    detail=fam,
                ))

    # SC304 / SC305 — dashboard.
    dash_path = cfg.resolve(cfg.dashboard_path)
    if dash_path is not None and dash_path.exists():
        dash = _dashboard_families(dash_path)
        dash_norm = {_normalize(f, registry) for f in dash}
        for fam, meta in sorted(registry.items()):
            if "dashboard" in meta.get("mirrors", ()) and fam not in dash_norm:
                out.append(Violation(
                    rule="SC304", file=cfg.dashboard_path, line=1,
                    qualname="metrics",
                    message=(
                        f"family `{fam}` is flagged for the dashboard but "
                        "no panel expr references it"
                    ),
                    detail=fam,
                ))
        for fam, panel in sorted(dash.items()):
            if _normalize(fam, registry) not in registry:
                out.append(Violation(
                    rule="SC305", file=cfg.dashboard_path, line=1,
                    qualname="metrics",
                    message=(
                        f"dashboard panel '{panel}' queries `{fam}`, which "
                        "the registry doesn't know (stale panel or missing "
                        "registry entry)"
                    ),
                    detail=fam,
                ))

    # SC306 / SC307 — docs.
    docs_path = cfg.resolve(cfg.docs_path)
    if docs_path is not None and docs_path.exists():
        docs_text = docs_path.read_text()
        doc_fams = _prose_families(docs_text)
        doc_norm = {_normalize(f, registry) for f in doc_fams}
        for fam, meta in sorted(registry.items()):
            if "docs" in meta.get("mirrors", ()) and fam not in doc_norm:
                out.append(Violation(
                    rule="SC306", file=cfg.docs_path, line=1,
                    qualname="metrics",
                    message=(
                        f"family `{fam}` is flagged for the docs table but "
                        f"{cfg.docs_path} never mentions it"
                    ),
                    detail=fam,
                ))
        for fam in sorted(doc_fams):
            base = _normalize(fam, registry)
            # Docs may legitimately name template placeholders like
            # tpu:step_{schedule,...}_seconds — the regex won't match
            # those, so anything matched but unknown is real drift.
            if base not in registry:
                out.append(Violation(
                    rule="SC307", file=cfg.docs_path, line=1,
                    qualname="metrics",
                    message=(
                        f"docs reference `{fam}`, which the registry "
                        "doesn't know"
                    ),
                    detail=fam,
                ))
    return out
