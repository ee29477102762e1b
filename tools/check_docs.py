"""Docs CI: validate markdown cross-links (relative paths + anchors) and
CLI-flag references.

Stdlib-only.  Scans every ``*.md`` in the repo (skipping generated build
dirs), extracts ``[text](target)`` links, and fails if

* a relative link points at a file that does not exist, or
* a ``path#anchor`` / ``#anchor`` fragment names a heading that is not
  present in the target file (GitHub-style slugs), or
* an inline-code CLI flag (`` `--pp ...` ``) names a flag no
  ``add_argument`` in the repo's entry points defines — stale flag docs
  (e.g. a renamed ``--pp``) fail instead of rotting, or
* a scheme-field / comm-tag token (``tp_fwd_inner``-shaped:
  ``<dim>_<fwd|bwd|inner|outer>...``) names a field the ``Scheme``
  dataclass no longer declares — docs referencing removed scheme fields
  fail instead of rotting (the field list is parsed from
  ``src/repro/core/schemes.py``, no import needed), or
* a codec-shaped inline-code token (``bq16``, ``gq8``, ``plr8``,
  ``ef:bq4``) names a codec the registry cannot construct: quantization
  rates are parsed from ``kernels/ref.py``/``core/codecs.py`` and the
  parameterized grammar (``ef:<lossy codec>``, ``plr<rank>``) is
  validated structurally — so ``ef:bq4`` is recognized as a valid
  parameterized codec, while a stale ``bq12`` or ``ef:none`` fails, or
* a documented ledger fact (``a `vpp` fact``) names a key no
  ``comms.scope_facts(...)`` call site actually attaches to ledger
  events — parsed from ``src/``, so renaming/dropping the fact in the
  pipeline breaks the doc reference instead of letting it rot.

``--xla*`` flags (XLA's own) are exempt.  External links (``http://`` /
``https://`` / ``mailto:``) are not fetched — CI must not depend on
network.  Run locally with::

    python tools/check_docs.py
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
SKIP_DIRS = {".git", ".github", "node_modules", "__pycache__", ".venv",
             "results", ".pytest_cache"}

# [text](target) — won't match ![img](...) differently (images are links
# too and should also resolve); ignores ```code fences``` via scrubbing.
_LINK_RE = re.compile(r"(?<!\!)\[[^\]]*\]\(([^)\s]+)\)")
_IMG_RE = re.compile(r"\!\[[^\]]*\]\(([^)\s]+)\)")
_HEADING_RE = re.compile(r"^#{1,6}\s+(.*)$", re.MULTILINE)
_FENCE_RE = re.compile(r"```.*?```", re.DOTALL)


def github_slug(heading: str) -> str:
    """GitHub's anchor slug: lowercase, drop punctuation, spaces->dashes."""
    h = heading.strip().lower()
    h = re.sub(r"[`*_]", "", h)              # inline markdown
    h = re.sub(r"[^\w\sÀ-￿-]", "", h)
    return re.sub(r"\s+", "-", h.strip())


def md_files():
    for p in sorted(ROOT.rglob("*.md")):
        if not any(part in SKIP_DIRS for part in p.parts):
            yield p


def anchors_of(path: pathlib.Path) -> set[str]:
    text = _FENCE_RE.sub("", path.read_text(encoding="utf-8"))
    out = set()
    for m in _HEADING_RE.finditer(text):
        slug = github_slug(m.group(1))
        # GitHub dedupes repeated headings as slug, slug-1, slug-2 ...
        cand = slug
        i = 1
        while cand in out:
            cand = f"{slug}-{i}"
            i += 1
        out.add(cand)
    return out


# `--flag` at the start of an inline code span (``--xla*`` belongs to XLA)
_FLAG_RE = re.compile(r"`(--[a-zA-Z][a-zA-Z0-9_-]*)")
# bare flags inside shell-ish fenced blocks (usage examples)
_SHELL_FENCE_RE = re.compile(r"```(?:bash|sh|shell|console)?\n(.*?)```",
                             re.DOTALL)
_BARE_FLAG_RE = re.compile(r"(?<![\w`=-])(--[a-zA-Z][a-zA-Z0-9_-]*)")
# fence lines are only checked when they invoke one of OUR entry points —
# third-party commands (pip, pytest, git...) carry their own flags
_OWN_CMD_RE = re.compile(r"repro\.|benchmarks[/.]|tools/|examples/")
# documented third-party flags that are fine in inline code spans
# (pytest's --durations shows the slowest tests in the CI tier-1 run)
_EXEMPT_FLAGS = {"--xla_force_host_platform_device_count", "--durations"}


def _flag_exempt(flag: str) -> bool:
    return flag.startswith("--xla") or flag in _EXEMPT_FLAGS
_ADD_ARG_RE = re.compile(r"add_argument\(\s*['\"](--[a-zA-Z][a-zA-Z0-9_-]*)")
_FLAG_SRC_DIRS = ("src", "bench", "benchmarks", "tools", "examples")


def defined_flags() -> set[str]:
    """Every CLI flag an add_argument in the repo's entry points defines."""
    out = set()
    for d in _FLAG_SRC_DIRS:
        root = ROOT / d
        if not root.exists():
            continue
        for p in sorted(root.rglob("*.py")):
            if any(part in SKIP_DIRS for part in p.parts):
                continue
            out |= set(_ADD_ARG_RE.findall(p.read_text(encoding="utf-8")))
    for p in sorted(ROOT.glob("*.py")):          # chip_smoke.py
        out |= set(_ADD_ARG_RE.findall(p.read_text(encoding="utf-8")))
    return out


def check_flags(src: pathlib.Path, text: str, known: set[str]) -> list[str]:
    flags = set(_FLAG_RE.findall(text))
    for block in _SHELL_FENCE_RE.findall(text):
        # multi-line commands: a backslash-continued line belongs to the
        # command started above it
        own = cont = False
        for line in block.splitlines():
            if not cont:
                own = bool(_OWN_CMD_RE.search(line))
            if own:
                flags |= set(_BARE_FLAG_RE.findall(line))
            cont = line.rstrip().endswith("\\")
    errors = []
    for flag in sorted(flags):
        if flag in known or _flag_exempt(flag):
            continue
        errors.append(f"{src.relative_to(ROOT)}: stale CLI flag "
                      f"reference {flag} (no add_argument defines it)")
    return errors


# a scheme-field-shaped token: a comm dimension plus one or more
# direction/level suffixes.  Deliberately narrow — bench row names like
# `tp_allreduce` or scheme names like `hier_zpp_8_16` never match.
_SCHEME_FIELD_RE = re.compile(
    r"\b(?:dp|zero|tp|pp|ep|cp|kv)(?:_(?:fwd|bwd|inner|outer))+\b")
_FIELD_DECL_RE = re.compile(r"^    (\w+): str(?:\s*\|\s*None)? =",
                            re.MULTILINE)


def scheme_fields() -> set[str]:
    """The Scheme dataclass's tag-field names, parsed (not imported) from
    src/repro/core/schemes.py — stdlib-only, like the rest of this
    checker."""
    src = (ROOT / "src" / "repro" / "core" / "schemes.py") \
        .read_text(encoding="utf-8")
    return set(_FIELD_DECL_RE.findall(src))


def check_scheme_tags(src: pathlib.Path, text: str,
                      known: set[str]) -> list[str]:
    errors = []
    for tok in sorted(set(_SCHEME_FIELD_RE.findall(text))):
        if tok not in known:
            errors.append(
                f"{src.relative_to(ROOT)}: stale scheme-field reference "
                f"`{tok}` (no such Scheme field / comm tag)")
    return errors


# a codec-shaped token inside an inline code span: quantization families
# with a rate suffix, low-rank plr<rank>, and ef:-prefixed wrappers.
# Deliberately narrow — scheme names like `hier_zpp_8_16` never match.
_CODEC_TOKEN_RE = re.compile(r"`((?:ef:)?(?:bq|gq|tq)\d+|ef:plr\d+|plr\d+"
                             r"|ef:(?:none|mpc|ef:[a-z0-9:]*))`")
_QMAX_RE = re.compile(r"_QMAX\s*=\s*\{([^}]*)\}")
_QINST_RE = re.compile(r"(Gq|Tq)Codec\(bits=(\d+)\)")
_MAX_RANK_RE = re.compile(r"MAX_RANK\s*=\s*(\d+)")


def codec_rates() -> dict:
    """Valid rates per quantization family, parsed (not imported) from
    the kernel/codec sources: ``bq`` rates from ref.py's _QMAX table,
    ``gq``/``tq`` from the instantiations codecs.py registers."""
    ref = (ROOT / "src" / "repro" / "kernels" / "ref.py") \
        .read_text(encoding="utf-8")
    m = _QMAX_RE.search(ref)
    bq = {int(k) for k in re.findall(r"(\d+)\s*:", m.group(1))} if m \
        else set()
    src = (ROOT / "src" / "repro" / "core" / "codecs.py") \
        .read_text(encoding="utf-8")
    fam = {"bq": bq, "gq": set(), "tq": set()}
    for f, bits in _QINST_RE.findall(src):
        fam[f.lower()].add(int(bits))            # Gq -> gq, Tq -> tq
    m = _MAX_RANK_RE.search(src)
    fam["plr_max"] = int(m.group(1)) if m else 64
    return fam


def _codec_token_valid(tok: str, rates: dict) -> bool:
    if tok.startswith("ef:"):
        inner = tok[3:]
        # ef wraps lossy, non-ef codecs only (mirrors codecs._parse)
        if inner in ("none", "mpc") or inner.startswith("ef:") or not inner:
            return False
        return _codec_token_valid(inner, rates)
    if tok.startswith("plr"):
        return tok[3:].isdigit() and 1 <= int(tok[3:]) <= rates["plr_max"]
    m = re.match(r"(bq|gq|tq)(\d+)$", tok)
    return bool(m) and int(m.group(2)) in rates[m.group(1)]


def check_codec_names(src: pathlib.Path, text: str,
                      rates: dict) -> list[str]:
    errors = []
    for tok in sorted(set(_CODEC_TOKEN_RE.findall(text))):
        if not _codec_token_valid(tok, rates):
            errors.append(
                f"{src.relative_to(ROOT)}: stale codec reference `{tok}` "
                f"(the registry cannot construct it)")
    return errors


# a documented ledger fact ("a `vpp` fact"): the token must be a key some
# scope_facts(...) call site actually merges into ledger events
_DOC_FACT_RE = re.compile(r"`(\w+)`\s+fact\b")
_SCOPE_FACTS_RE = re.compile(r"scope_facts\(([^)]*)\)")
_KWARG_RE = re.compile(r"(\w+)\s*=")


_EV_KEY_RE = re.compile(r"ev\[['\"](\w+)['\"]\]\s*=")


def ledger_facts() -> set[str]:
    """Fact keys the runtime attaches to ledger events, parsed (not
    imported) from ``src/``: the kwargs of every ``scope_facts(...)``
    call site, plus keys ``comms._account`` assigns onto the event dict
    directly (``ev["ring"] = ...``)."""
    out = set()
    for p in sorted((ROOT / "src").rglob("*.py")):
        if any(part in SKIP_DIRS for part in p.parts):
            continue
        text = p.read_text(encoding="utf-8")
        for args in _SCOPE_FACTS_RE.findall(text):
            out |= set(_KWARG_RE.findall(args))
        out |= set(_EV_KEY_RE.findall(text))
    return out


def check_ledger_facts(src: pathlib.Path, text: str,
                       known: set[str]) -> list[str]:
    errors = []
    for tok in sorted(set(_DOC_FACT_RE.findall(text))):
        if tok not in known:
            errors.append(
                f"{src.relative_to(ROOT)}: stale ledger-fact reference "
                f"`{tok}` (no scope_facts call site attaches it)")
    return errors


# a documented tune_policy.json field ("the `plan_hash` artifact field"):
# the token must be a member of policy_artifact.py's ARTIFACT_FIELDS or
# RULE_FIELDS tuples — renaming an artifact field breaks the doc
# reference instead of letting it rot
_DOC_ART_FIELD_RE = re.compile(r"`(\w+)`\s+artifact\s+field\b")
_ART_FIELDS_RE = re.compile(
    r"(?:ARTIFACT_FIELDS|RULE_FIELDS)\s*=\s*\(([^)]*)\)")


def artifact_fields() -> set[str]:
    """tune_policy.json's field names, parsed (not imported) from
    src/repro/tune/policy_artifact.py."""
    src = (ROOT / "src" / "repro" / "tune" / "policy_artifact.py")
    if not src.exists():
        return set()
    out = set()
    for body in _ART_FIELDS_RE.findall(src.read_text(encoding="utf-8")):
        out |= set(re.findall(r"['\"](\w+)['\"]", body))
    return out


def check_artifact_fields(src: pathlib.Path, text: str,
                          known: set[str]) -> list[str]:
    errors = []
    for tok in sorted(set(_DOC_ART_FIELD_RE.findall(text))):
        if tok not in known:
            errors.append(
                f"{src.relative_to(ROOT)}: stale tune_policy.json field "
                f"reference `{tok}` (not in ARTIFACT_FIELDS/RULE_FIELDS)")
    return errors


def check() -> list[str]:
    errors = []
    known_flags = defined_flags()
    known_fields = scheme_fields()
    known_rates = codec_rates()
    known_facts = ledger_facts()
    known_art = artifact_fields()
    for src in md_files():
        raw = src.read_text(encoding="utf-8")
        text = _FENCE_RE.sub("", raw)
        # flags are checked in fenced blocks too — usage examples live there
        errors += check_flags(src, raw, known_flags)
        errors += check_scheme_tags(src, raw, known_fields)
        errors += check_codec_names(src, raw, known_rates)
        errors += check_ledger_facts(src, raw, known_facts)
        errors += check_artifact_fields(src, raw, known_art)
        targets = [m.group(1) for m in _LINK_RE.finditer(text)]
        targets += [m.group(1) for m in _IMG_RE.finditer(text)]
        for t in targets:
            if t.startswith(("http://", "https://", "mailto:")):
                continue
            path_part, _, frag = t.partition("#")
            if path_part:
                dest = (src.parent / path_part).resolve()
                if not dest.exists():
                    errors.append(f"{src.relative_to(ROOT)}: broken link "
                                  f"-> {t}")
                    continue
            else:
                dest = src
            if frag and dest.suffix == ".md":
                if frag.lower() not in anchors_of(dest):
                    errors.append(f"{src.relative_to(ROOT)}: missing anchor "
                                  f"#{frag} in {dest.relative_to(ROOT)}")
    return errors


def main() -> int:
    errors = check()
    n = len(list(md_files()))
    if errors:
        print("\n".join(errors), file=sys.stderr)
        print(f"docs check FAILED: {len(errors)} broken link(s) across "
              f"{n} markdown files", file=sys.stderr)
        return 1
    print(f"docs check OK: {n} markdown files, all relative links + "
          "anchors resolve")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
