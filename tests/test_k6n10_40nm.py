"""``arch.builtin.k6_n10_40nm_arch``: the routing architecture of VTR's
k6_N10_40nm.xml as DATA -- every published number, the sb / cb patterns
the XML parser now reads (and refuses when the rr builder cannot
realise them), non-equivalent cluster outputs -- and the span and
gauges ``build_rr_graph`` reports about a single-driver graph."""

import os
import warnings

import numpy as np
import pytest

from parallel_eda_tpu.arch.builtin import k6_n10_40nm_arch, k6_n10_arch
from parallel_eda_tpu.arch.model import PIN_CLASS_DRIVER, SegmentInf
from parallel_eda_tpu.arch.xml_parser import read_arch_xml
from parallel_eda_tpu.obs import Tracer, get_metrics, set_tracer
from parallel_eda_tpu.rr.graph import SOURCE, build_rr_graph
from parallel_eda_tpu.rr.grid import DeviceGrid

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "k6_frac_n10_mem.xml")


def test_builder_states_the_published_routing_architecture():
    """Every number of the published file, and the same as the XML
    parser reads from the offline copy of its routing half."""
    arch = k6_n10_40nm_arch(chan_width=64)
    assert (arch.K, arch.N, arch.I, arch.io_capacity) == (6, 10, 33, 8)
    assert (arch.Fc_in, arch.Fc_out) == (0.15, 0.10)
    (seg,) = arch.segments
    assert (seg.length, seg.directionality) == (4, "unidir")
    assert seg.sb == (1, 1, 1, 1, 1) and seg.cb == (1, 1, 1, 1)
    assert (seg.Rmetal, seg.Cmetal) == (101.0, 22.5e-15)
    assert seg.wire_switch == seg.opin_switch
    mux = arch.switches[seg.wire_switch]
    assert (mux.R, mux.Tdel, mux.Cin, mux.Cout) == (551.0, 58e-12,
                                                    0.77e-15, 4e-15)
    assert arch.switches[arch.ipin_switch].Tdel == 7.247e-11
    assert (arch.sb_type, arch.sb_fs) == ("wilton", 3)
    assert arch.default_chan_width == 64

    xml = read_arch_xml(GOLDEN)
    (xseg,) = xml.segments
    for f in ("length", "directionality", "sb", "cb", "Rmetal", "Cmetal"):
        assert getattr(xseg, f) == getattr(seg, f), f
    xmux = xml.switches[xseg.wire_switch]
    for f in ("R", "Tdel", "Cin", "Cout"):
        assert getattr(xmux, f) == getattr(mux, f), f
    assert (xml.Fc_in, xml.Fc_out) == (arch.Fc_in, arch.Fc_out)
    # the tests' fixture keeps its length-1 bidirectional wire
    assert k6_n10_arch().segments[0].directionality == "bidir"


@pytest.mark.parametrize("kw, why", [
    (dict(length=4, sb=(1, 1, 1, 1)), "has 4 marks"),
    (dict(length=4, sb=(1, 1, 1, 1, 0)), "wire end"),
    (dict(length=4, sb=(0, 1, 1, 1, 1)), "wire end"),
    (dict(length=4, cb=(1, 0, 1, 1)), "depopulated"),
    (dict(length=4, cb=(1, 1, 1)), "has 3 marks"),
    (dict(length=4, sb=(1, 0, 0, 0, 1), directionality="bidir"),
     "bidirectional"),
])
def test_a_pattern_the_builder_cannot_realise_is_refused(kw, why):
    kw.setdefault("directionality", "unidir")
    with pytest.raises(ValueError, match=why):
        SegmentInf(**kw)


def test_xml_patterns_are_read_not_dropped(tmp_path):
    """<sb> and <cb> reach the segment; an end-only sb parses, a
    depopulated cb is refused where it is read."""
    text = open(GOLDEN).read()
    end_only = tmp_path / "end_only.xml"
    end_only.write_text(text.replace("1 1 1 1 1</sb>", "1 0 0 0 1</sb>"))
    assert read_arch_xml(str(end_only)).segments[0].sb == (1, 0, 0, 0, 1)
    depop = tmp_path / "depop.xml"
    depop.write_text(text.replace("1 1 1 1</cb>", "1 0 0 1</cb>"))
    with pytest.raises(ValueError, match="depopulated"):
        read_arch_xml(str(depop))


def _build(arch, n=5, W=16):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the file asks for Wilton
        return build_rr_graph(arch, DeviceGrid(n, n, arch.io_capacity), W)


def test_cluster_outputs_are_not_equivalent():
    """k6_N10_40nm.xml: ``<output name="O" num_pins="10"
    equivalent="false"/>``.  Each output pin is a class of its own, so
    a net has ONE OPIN and a full cluster cannot lose a pin to a net
    that branches at its SOURCE (PERF.md PR 26: the lone over-used
    perimeter OPIN)."""
    arch = k6_n10_40nm_arch(chan_width=16)
    clb = arch.clb_type
    drivers = [c for c in clb.pin_classes
               if c.direction == PIN_CLASS_DRIVER]
    assert len(drivers) == 10 and all(len(c.pins) == 1 for c in drivers)
    assert sorted(p for c in drivers for p in c.pins) == list(
        range(33, 43))
    assert [clb.pin_classes[clb.pin_class_of[p]].pins for p in
            range(33, 43)] == [[p] for p in range(33, 43)]
    assert len(clb.pin_classes[0].pins) == 33       # inputs: one class
    rr = _build(arch)
    src = rr.node_type == SOURCE
    clb_src = src & (rr.xlow >= 1) & (rr.xlow <= 5) & (rr.ylow >= 1) & (
        rr.ylow <= 5)
    assert clb_src.sum() == 25 * 10 and (rr.capacity[clb_src] == 1).all()
    assert (np.diff(rr.out_row_ptr)[clb_src] == 1).all()


def test_rr_build_span_and_gauges(tmp_path):
    """``rr.build`` carries what was built; a unidir graph sets the two
    gauges that were silently zero or shared before PR 26."""
    reg = get_metrics()
    was = reg.enabled
    reg.enabled = True
    tr = Tracer(str(tmp_path / "t.json"))
    set_tracer(tr)
    try:
        rr = _build(k6_n10_40nm_arch(chan_width=16))
        values = reg.values("rr.")
    finally:
        set_tracer(None)
        reg.enabled = was
    (ev,) = [e for e in tr.events if e.get("name") == "rr.build"]
    assert ev["args"] == {"unidir": True, "W": 16, "max_span": 4,
                          "nodes": rr.num_nodes, "edges": rr.num_edges,
                          "block_types": 2, "hard_columns": 0,
                          "tall_rows": 1}
    assert values["rr.exit_turns_min"] == 2
    assert values["rr.opin_starts_min"] == 2        # round(0.10 x 16)
