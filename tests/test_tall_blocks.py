"""Typed columns and tall blocks on the normal path: a block ``height``
through the grid, the rr graph, the net terminals and both placers; a
hard block's pins one class each; the parser's ``height``; and the
published heterogeneous architecture's routing half held equal to
``k6_n10_40nm_arch``'s."""

import dataclasses
import warnings

import numpy as np
import pytest

from parallel_eda_tpu.arch.builtin import (k6_frac_n10_mem32k_40nm_arch,
                                           k6_n10_40nm_arch, unidir_arch)
from parallel_eda_tpu.arch.model import (PIN_CLASS_DRIVER, ColumnSpec,
                                         make_hard_type)
from parallel_eda_tpu.rr.graph import (IPIN, OPIN, SINK, SOURCE,
                                       build_rr_graph, check_rr_graph)
from parallel_eda_tpu.rr.grid import make_grid, size_grid


def _tall_arch(height: int, W: int = 8):
    """The tests' length-1 single-driver fixture with one hard type of
    ``height`` rows (5 inputs, 3 outputs) on columns 2, 5, ..."""
    arch = unidir_arch(chan_width=W)
    arch.block_types.append(make_hard_type(
        "ram", index=2, num_in=5, num_out=3, height=height))
    arch.column_types = [ColumnSpec("ram", start=2, repeat=3)]
    arch.hard_models = {"spram": "ram"}
    return arch


@pytest.mark.parametrize("height", [2, 3])
def test_height_through_grid_and_rr_graph(height):
    """A 2-row and a 3-row block beside clusters: anchors every
    ``height`` rows, ny // height blocks a column and the rows left
    over empty; SOURCE / SINK once a block, spanning its rows; pin p on
    row p % height, reaching the four channels beside that row."""
    arch = _tall_arch(height)
    grid = make_grid(arch, 6, 7)
    assert grid.col_types == {2: "ram", 5: "ram"}
    anchors = [1 + k * height for k in range(7 // height)]
    assert grid.anchor_rows("ram") == anchors
    assert grid.sites_of_type("ram") == [(x, y) for y in anchors
                                         for x in (2, 5)]
    assert grid.block_at(2, anchors[-1] + height - 1) == ("ram",
                                                          anchors[-1])
    if 7 % height:
        assert grid.block_at(2, 7) is None       # left over: empty
    assert grid.block_at(1, 7) == ("clb", 7)
    assert len(grid.sites_of_type("clb")) == 4 * 7

    rr = build_rr_graph(arch, grid)
    check_rr_graph(rr, arch=arch)
    bt = arch.block_type("ram")
    at = lambda kind, x: ((rr.node_type == kind) & (rr.xlow == x)
                          & (rr.ylow >= 1) & (rr.ylow <= 7))
    for x in (2, 5):
        # one SOURCE a driver pin and one SINK a receiver pin (the
        # clock's too) per BLOCK, each spanning the block's rows
        for kind, n in ((SOURCE, 3), (SINK, 6)):
            m = at(kind, x)
            assert m.sum() == n * len(anchors)
            assert sorted(set(rr.ylow[m])) == anchors
            assert np.all(rr.yhigh[m] - rr.ylow[m] == height - 1)
        for y in range(1, 8):
            site = grid.block_at(x, y)
            pins = ([] if site is None else
                    [p for p in range(bt.num_pins)
                     if p % height == y - site[1]])
            for kind, is_out in ((OPIN, True), (IPIN, False)):
                m = at(kind, x) & (rr.ylow == y)
                want = [p for p in pins if (5 <= p < 8) == is_out]
                assert sorted(rr.ptc[m]) == want
                assert np.all(rr.yhigh[m] == y)
    # a pin reaches wires of the channels beside ITS row only
    for (x, y0, z, p), node in rr.opin_of.items():
        if grid.is_clb(x, y0) and grid.interior_type_name(x) == "ram":
            row = y0 + p % height
            assert rr.ylow[node] == row
            wires = rr.out_dst[rr.out_row_ptr[node]:
                               rr.out_row_ptr[node + 1]]
            assert len(wires)
            assert np.all((rr.ylow[wires] <= row)
                          & (rr.yhigh[wires] >= row - 1))
    # size_grid counts ny // height blocks a column
    # (columns 2 and 5: 5 x 5 holds 2 x 2 blocks of two rows, 6 x 6
    # holds 2 x 2 of three; 5 x 5 only 2 x 1 of those)
    g = size_grid(4, 4, arch, hard_counts={"ram": 3})
    assert (g.nx, g.ny) == {2: (5, 5), 3: (6, 6)}[height]
    assert len(g.sites_of_type("ram")) == 4


def test_hard_block_pins_are_one_class_each():
    bt = make_hard_type("ram", index=2, num_in=5, num_out=3, height=2)
    assert bt.height == 2 and bt.num_pins == 9
    assert bt.pin_class_of == list(range(9))
    assert [c.pins for c in bt.pin_classes] == [[p] for p in range(9)]
    assert [c.direction == PIN_CLASS_DRIVER for c in bt.pin_classes] == (
        [False] * 5 + [True] * 3 + [False])
    assert bt.pin_classes[8].is_clock
    assert bt.num_input_pins == 5 and bt.num_output_pins == 3
    # so the rr graph has one SINK of capacity 1 per input pin
    arch = _tall_arch(2)
    rr = build_rr_graph(arch, make_grid(arch, 3, 4))
    ram = (rr.xlow == 2) & ((rr.node_type == SINK)
                            | (rr.node_type == SOURCE))
    assert np.all(rr.capacity[ram] == 1)


def _placed_flow(seed=3):
    from parallel_eda_tpu.flow import prepare
    from parallel_eda_tpu.netlist.synthesis import ram_pipeline

    arch = _tall_arch(3, W=12)
    nl = ram_pipeline(n_mems=3, addr_bits=2, data_bits=2)
    return prepare(nl, arch, chan_width=12, seed=seed, nx=6, ny=7)


@pytest.mark.parametrize("placer", ["native", "device"])
def test_placers_keep_every_block_on_its_types_anchors(placer):
    """Over a seeded anneal, hot enough to move every block: each block
    ends on a column of its own type, a tall one on an anchor row, no
    two on one site (``check_place``), and the RAMs did move."""
    from parallel_eda_tpu.flow import run_place, run_place_native
    from parallel_eda_tpu.place.check import check_place
    from parallel_eda_tpu.place.sa import PlacerOpts

    f = _placed_flow()
    check_place(f.pnl, f.grid, f.pos)
    before = f.pos.copy()
    if placer == "native":
        f = run_place_native(f, seed=7)
    else:
        f = run_place(f, PlacerOpts(moves_per_step=32, max_temps=12,
                                    seed=7), timing_driven=False)
    check_place(f.pnl, f.grid, f.pos)
    rams = [i for i, b in enumerate(f.pnl.blocks) if b.type_name == "ram"]
    assert len(rams) == 3
    for i in rams:
        x, y, z = f.pos[i]
        assert f.grid.interior_type_name(int(x)) == "ram"
        assert int(y) in f.grid.anchor_rows("ram") and z == 0
    for i, b in enumerate(f.pnl.blocks):
        if b.type_name == "clb":
            assert f.grid.interior_type_name(int(f.pos[i, 0])) == "clb"
    assert (f.pos != before).any()
    # the terminals follow: a RAM net's box holds the block's 3 rows
    t = f.term
    assert t.hard is not None and t.hard.any()
    r = int(np.flatnonzero(t.hard)[0])
    assert t.bb_ymax[r] - t.bb_ymin[r] + 1 >= 3


def test_check_place_refuses_a_tall_block_off_its_anchor():
    from parallel_eda_tpu.place.check import check_place

    f = _placed_flow()
    ram = next(i for i, b in enumerate(f.pnl.blocks)
               if b.type_name == "ram")
    pos = f.pos.copy()
    pos[ram, 1] += 1
    with pytest.raises(ValueError, match="anchor rows"):
        check_place(f.pnl, f.grid, pos)
    pos = f.pos.copy()
    pos[ram, 0] = 1
    with pytest.raises(ValueError, match="column of another type"):
        check_place(f.pnl, f.grid, pos)


XML = """<architecture>
  <switchlist>
    <switch type="mux" name="0" R="551" Cin="7.7e-15" Cout="12.9e-15" Tdel="58e-12"/>
  </switchlist>
  <segmentlist>
    <segment freq="1" length="1" Rmetal="101" Cmetal="22.5e-15"><mux name="0"/></segment>
  </segmentlist>
  <complexblocklist>
    <pb_type name="io" capacity="8"/>
    <pb_type name="clb"{clb_height}>
      <input name="I" num_pins="33"/>
      <output name="O" num_pins="10"/>
      <pb_type name="ble"><pb_type name="lut" blif_model=".names">
        <input name="in" num_pins="6"/><output name="out" num_pins="1"/>
      </pb_type></pb_type>
    </pb_type>
    <pb_type name="mult_36" height="4" blif_model=".subckt multiply">
      <input name="a" num_pins="36"/>
      <input name="b" num_pins="36"/>
      <output name="out" num_pins="72"/>
      <gridlocations><loc type="col" start="4" repeat="8"/></gridlocations>
    </pb_type>
  </complexblocklist>
</architecture>"""


def test_xml_parser_reads_height(tmp_path):
    from parallel_eda_tpu.arch.xml_parser import read_arch_xml

    p = tmp_path / "arch.xml"
    p.write_text(XML.format(clb_height=""))
    arch = read_arch_xml(str(p))
    bt = arch.block_type("mult_36")
    assert bt.height == 4
    assert (bt.num_input_pins, bt.num_output_pins) == (72, 72)
    assert arch.clb_type.height == 1
    assert make_grid(arch, 8, 9).anchor_rows("mult_36") == [1, 5]
    # a tall logic cluster is refused, not dropped
    p.write_text(XML.format(clb_height=' height="2"'))
    with pytest.raises(ValueError, match="height 2"):
        read_arch_xml(str(p))


def test_k6_frac_routing_half_equals_k6_n10_40nm():
    """The heterogeneous architecture's routing numbers are
    ``k6_n10_40nm_arch``'s to the last one; its blocks are the
    published ones."""
    a, b = k6_frac_n10_mem32k_40nm_arch(64), k6_n10_40nm_arch(64)
    for key in ("segments", "switches", "Fc_in", "Fc_out", "Fc_in_abs",
                "Fc_out_abs", "ipin_switch", "default_chan_width",
                "sb_type", "sb_fs", "io_capacity", "K", "N", "directs"):
        assert getattr(a, key) == getattr(b, key), key
    assert dataclasses.asdict(a.io_type) == dataclasses.asdict(b.io_type)
    clb = a.clb_type
    assert (a.I, clb.num_input_pins, clb.num_output_pins) == (40, 40, 20)
    assert len(clb.pin_classes[0].pins) == 40       # inputs equivalent
    assert sum(c.direction == PIN_CLASS_DRIVER and len(c.pins) == 1
               for c in clb.pin_classes) == 20      # outputs are not
    mult, mem = a.block_type("mult_36"), a.block_type("memory")
    assert (mult.height, mult.num_input_pins, mult.num_output_pins) == (
        4, 72, 72)
    assert (mem.height, mem.num_input_pins, mem.num_output_pins) == (
        6, 96, 64)
    assert [(c.type_name, c.start, c.repeat) for c in a.column_types] == [
        ("memory", 2, 8), ("mult_36", 4, 8)]
    assert a.hard_models == {"multiply": "mult_36",
                             "dual_port_ram": "memory"}
    grid = make_grid(a, 25, 25)
    assert sorted(grid.col_types) == [2, 4, 10, 12, 18, 20]
    assert len(grid.sites_of_type("memory")) == 3 * 4
    assert len(grid.sites_of_type("mult_36")) == 3 * 6
    assert len(grid.sites_of_type("clb")) == 19 * 25
    # the wire nodes do not know the blocks: same channels either way
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # the file asks for Wilton
        ra = build_rr_graph(k6_frac_n10_mem32k_40nm_arch(16),
                            make_grid(a, 8, 8))
        rb = build_rr_graph(k6_n10_40nm_arch(16), make_grid(b, 8, 8))
    wa, wb = ra.node_type >= 4, rb.node_type >= 4
    for key in ("node_type", "xlow", "xhigh", "ylow", "yhigh", "ptc",
                "R", "C"):
        assert np.array_equal(getattr(ra, key)[wa], getattr(rb, key)[wb])
    check_rr_graph(ra, arch=k6_frac_n10_mem32k_40nm_arch(16))
