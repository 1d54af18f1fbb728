"""Docs drift guard: the engine-mode, workload, metadata-residency,
admission-policy, SLO, and reliability tables in DESIGN.md §2/§3/§6/§7
and README.md duplicate each other by design (one is the architecture
doc, one the landing page); these tests keep both in lockstep with
``MODES``, the plan layer's ``WORKLOADS``, the persistent megakernel's
``META_LAYOUTS``, the quantizer's ``META_FORMATS``, the batcher's
``ADMISSION_KNOBS``, the serve
harness's ``SLO_METRICS``/``RELIABILITY_METRICS``, and the fault
harness's ``FAILURE_MODES``."""
import dataclasses
import os
import re

from repro.core.counters import Counters
from repro.core.wavefront import MODES
from repro.engine.batcher import ADMISSION_KNOBS
from repro.engine.faults import FAILURE_MODES
from repro.core.quantize import META_FORMATS
from repro.engine.plan import QueryPlan, WORKLOADS
from repro.kernels.persist.ops import (MAX_TILE_BQ, META_LAYOUTS,
                                       SUB_WINDOW_ROWS)
from repro.launch.serve import RELIABILITY_METRICS, SLO_METRICS

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _mode_table_cells(path: str) -> set:
    """Backticked first-column entries of markdown table rows (tables may
    be indented when they live inside a list item, e.g. DESIGN.md §3's
    residency table)."""
    cells = set()
    with open(os.path.join(_ROOT, path)) as f:
        for line in f:
            m = re.match(r"\s*\|\s*`([a-z0-9_]+)`\s*\|", line)
            if m:
                cells.add(m.group(1))
    return cells


def test_design_mode_table_lists_every_mode():
    cells = _mode_table_cells("DESIGN.md")
    for mode in MODES:
        assert mode in cells, f"DESIGN.md §2 table is missing `{mode}`"


def test_readme_mode_table_lists_every_mode():
    cells = _mode_table_cells("README.md")
    for mode in MODES:
        assert mode in cells, f"README engine-mode table is missing `{mode}`"


def test_design_workload_table_lists_every_plan_kind():
    cells = _mode_table_cells("DESIGN.md")
    for kind in WORKLOADS:
        assert kind in cells, f"DESIGN.md §2 workload table misses `{kind}`"


def test_readme_workload_table_lists_every_plan_kind():
    cells = _mode_table_cells("README.md")
    for kind in WORKLOADS:
        assert kind in cells, f"README workload table is missing `{kind}`"


def test_design_residency_table_lists_every_meta_layout():
    cells = _mode_table_cells("DESIGN.md")
    for layout in META_LAYOUTS:
        assert layout in cells, \
            f"DESIGN.md §3 residency/streaming table misses `{layout}`"


def test_readme_residency_table_lists_every_meta_layout():
    cells = _mode_table_cells("README.md")
    for layout in META_LAYOUTS:
        assert layout in cells, \
            f"README residency/streaming table is missing `{layout}`"


def test_design_format_table_lists_every_meta_format():
    cells = _mode_table_cells("DESIGN.md")
    for fmt in META_FORMATS:
        assert fmt in cells, \
            f"DESIGN.md §3 META_FORMATS table misses `{fmt}`"


def test_readme_format_table_lists_every_meta_format():
    cells = _mode_table_cells("README.md")
    for fmt in META_FORMATS:
        assert fmt in cells, \
            f"README compressed-metadata table is missing `{fmt}`"


def test_design_serving_section_lists_knobs_and_slos():
    cells = _mode_table_cells("DESIGN.md")
    for knob in ADMISSION_KNOBS:
        assert knob in cells, f"DESIGN.md §6 admission table misses `{knob}`"
    for metric in SLO_METRICS:
        assert metric in cells, f"DESIGN.md §6 SLO table misses `{metric}`"


def test_readme_service_section_lists_knobs_and_slos():
    cells = _mode_table_cells("README.md")
    for knob in ADMISSION_KNOBS:
        assert knob in cells, f"README admission table misses `{knob}`"
    for metric in SLO_METRICS:
        assert metric in cells, f"README SLO table misses `{metric}`"


def test_design_reliability_section_lists_failure_modes_and_counters():
    cells = _mode_table_cells("DESIGN.md")
    for mode in FAILURE_MODES:
        assert mode in cells, \
            f"DESIGN.md §7 failure-mode table misses `{mode}`"
    for metric in RELIABILITY_METRICS:
        assert metric in cells, \
            f"DESIGN.md §7 reliability-counters table misses `{metric}`"


def test_readme_reliability_section_lists_counters():
    cells = _mode_table_cells("README.md")
    for metric in RELIABILITY_METRICS:
        assert metric in cells, \
            f"README service-reliability table misses `{metric}`"


# -- persistent kernel-arm coverage (DESIGN.md §2 table + §3 schedule) --

# The optional QueryPlan lanes the §2 coverage table must map to kernel
# mechanisms.  Listed explicitly (rather than via dataclasses.fields) so
# a *new* optional lane fails the guard below until both the table and
# this tuple are updated.
_PLAN_LANES = ("scene_of_query", "owner_of_query", "payload")


def _flat_text(path: str) -> str:
    """File contents with runs of whitespace collapsed, so guards match
    across markdown line wraps."""
    with open(os.path.join(_ROOT, path)) as f:
        return re.sub(r"\s+", " ", f.read())


def test_design_coverage_table_lists_every_plan_lane():
    plan_fields = {f.name for f in dataclasses.fields(QueryPlan)}
    cells = _mode_table_cells("DESIGN.md")
    for lane in _PLAN_LANES:
        assert lane in plan_fields, f"QueryPlan lost lane `{lane}`"
        assert lane in cells, \
            f"DESIGN.md §2 kernel-arm coverage table misses `{lane}`"


def test_docs_name_the_fallback_counter():
    assert "ref_arm_fallbacks" in {f.name
                                   for f in dataclasses.fields(Counters)}
    for path in ("DESIGN.md", "README.md"):
        assert "ref_arm_fallbacks" in _flat_text(path), \
            f"{path} no longer documents Counters.ref_arm_fallbacks"


def test_design_window_constants_match_code():
    text = _flat_text("DESIGN.md")
    assert f"`SUB_WINDOW_ROWS` = {SUB_WINDOW_ROWS}" in text, \
        "DESIGN.md §3 window-schedule bullet disagrees with SUB_WINDOW_ROWS"
    assert "2 * (SUB_WINDOW_ROWS + 128)" in text, \
        "DESIGN.md no longer states the constant ping/pong VMEM footprint"
    assert f"`MAX_TILE_BQ` = {MAX_TILE_BQ}" in text, \
        "DESIGN.md §3 owner-tiling paragraph disagrees with MAX_TILE_BQ"
    assert f"`MAX_TILE_BQ` ({MAX_TILE_BQ})" in text, \
        "DESIGN.md §2 coverage table's capability bound disagrees with code"


def test_readme_window_constants_match_code():
    text = _flat_text("README.md")
    assert f"{SUB_WINDOW_ROWS} rows/slot" in text, \
        "README streamed-row cell disagrees with SUB_WINDOW_ROWS"
    assert f"wider than {MAX_TILE_BQ} slots" in text, \
        "README one-code-path paragraph disagrees with MAX_TILE_BQ"
