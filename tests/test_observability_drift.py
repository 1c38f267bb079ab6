"""Drift guards for the observability surface (ISSUE 19).

Two classes of silent rot are pinned here:

* the metrics catalog embedded in docs/observability.md must be the
  byte-exact output of ``tools/metrics_catalog.py`` — adding a family
  without regenerating the docs fails tier-1;
* the span/marker tables in ``tools/trace_report.py`` (and the event
  names ``tools/fleet_report.py`` keys its critical path on) must
  match the names the instrumented modules actually emit — renaming
  an event without updating the report tables would silently drop it
  from every report.
"""
import importlib.util
import os
import re

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_ROOT, "cometbft_tpu")


def _load(mod_name):
    spec = importlib.util.spec_from_file_location(
        mod_name, os.path.join(_ROOT, "tools", f"{mod_name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCatalogDocsDrift:
    def test_docs_catalog_matches_generator(self):
        cat = _load("metrics_catalog")
        generated = cat.to_markdown(cat.collect_catalog()).strip()
        with open(os.path.join(_ROOT, "docs",
                               "observability.md")) as f:
            doc = f.read()
        m = re.search(r"<!-- catalog:generated -->\n(.*?)\n"
                      r"<!-- /catalog:generated -->", doc, re.S)
        assert m, "catalog markers missing from docs/observability.md"
        assert m.group(1).strip() == generated, (
            "docs/observability.md catalog is stale — regenerate "
            "with: python tools/metrics_catalog.py")


def _emitted(category: str) -> tuple[set, set]:
    """(span_names, instant_names) for one category, by scanning the
    package source for tracing calls.  F-string names are truncated
    at the first placeholder (``step:{...}`` -> ``step:``)."""
    call = re.compile(
        r"tracing\.(instant|span|timed|record_span)\(\s*"
        r"tracing\.([A-Z0-9_]+)\s*,\s*[fF]?\"([^\"]+)\"", re.S)
    spans: set = set()
    instants: set = set()
    for dirpath, _dirs, files in os.walk(_PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                src = f.read()
            for kind, cat_const, name in call.findall(src):
                if cat_const != category:
                    continue
                name = name.split("{")[0]
                (instants if kind == "instant" else spans).add(name)
    return spans, instants


class TestTraceReportNamePinning:
    def test_consensus_spans_all_bucketed(self):
        tr = _load("trace_report")
        spans, _ = _emitted("CONSENSUS")
        assert spans, "census found no consensus spans"
        for name in spans:
            if name.startswith("step:"):
                continue
            assert name in tr.CONSENSUS_SPAN_BUCKETS, (
                f"consensus span {name!r} is emitted but has no "
                f"bucket in trace_report.CONSENSUS_SPAN_BUCKETS")
        # and the reverse: no stale table entries for names nobody
        # emits any more ("step:Commit" is matched dynamically)
        for name in tr.CONSENSUS_SPAN_BUCKETS:
            assert name in spans or name.startswith("step:"), (
                f"trace_report buckets {name!r} but nothing emits it")

    def test_state_spans_all_bucketed(self):
        """The executor and the block store own validate_block,
        store_save_block and apply_block (consensus wraps them no
        more): the report reads them from the state ring."""
        tr = _load("trace_report")
        spans, _ = _emitted("STATE")
        assert spans == set(tr.STATE_SPAN_BUCKETS), (
            f"emitted-only={sorted(spans - set(tr.STATE_SPAN_BUCKETS))}"
            f" table-only={sorted(set(tr.STATE_SPAN_BUCKETS) - spans)}")
        consensus, _ = _emitted("CONSENSUS")
        assert not consensus & spans, (
            "one span per boundary: a name the state ring has is "
            "not opened again by consensus")

    def test_runtime_spans_are_what_the_hook_records(self):
        """The runtime category has one writer, the recorder's own
        collection hook (libs/tracing.py), which records through
        ``r.record`` and not through the module-level API the census
        above scans for."""
        tr = _load("trace_report")
        with open(os.path.join(_PKG, "libs", "tracing.py")) as f:
            src = f.read()
        emitted = set(re.findall(
            r"\.record\(\s*RUNTIME\s*,\s*\"([^\"]+)\"", src))
        assert emitted == set(tr.RUNTIME_SPANS), (
            emitted, tr.RUNTIME_SPANS)
        for spans in (_emitted("RUNTIME")):
            assert not spans, "a second writer of the runtime category"

    def test_consensus_instants_all_marked(self):
        tr = _load("trace_report")
        _, instants = _emitted("CONSENSUS")
        assert instants, "census found no consensus instants"
        assert instants == set(tr.CONSENSUS_MARKERS), (
            "trace_report.CONSENSUS_MARKERS out of sync with the "
            f"emitted names: emitted-only="
            f"{sorted(instants - set(tr.CONSENSUS_MARKERS))} "
            f"table-only="
            f"{sorted(set(tr.CONSENSUS_MARKERS) - instants)}")

    def test_fleet_report_keys_on_emitted_names(self):
        """The cluster critical path is keyed on these instants; if
        one is renamed at the emit site the fleet report silently
        loses that column."""
        spans, instants = _emitted("CONSENSUS")
        for needed in ("proposal_broadcast", "proposal_recv",
                       "vote_recv", "commit"):
            assert needed in instants, needed
        assert "step:" in spans  # step:{...} spans incl. Propose

    def test_peer_attributed_mempool_instants_emitted(self):
        _, instants = _emitted("MEMPOOL")
        for needed in ("txs_recv", "have_recv", "want_recv"):
            assert needed in instants, needed
