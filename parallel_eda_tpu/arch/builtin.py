"""Built-in architectures.

The driver's config ladder (BASELINE.md) starts at ``k6_N10_40nm``:
``k6_n10_40nm_arch`` is that file's routing architecture (length-4
single-driver wires at the published Fc), built without an XML file;
``k6_n10_arch`` is the tests' length-1 bidirectional fixture with the
same cluster.  The reference tree bundles no arch XMLs: the published
numbers are those of ``tests/golden/k6_frac_n10_mem.xml:23-46``.
"""

from __future__ import annotations

from .model import (Arch, ColumnSpec, SegmentInf, SwitchInf, make_clb_type,
                    make_hard_type, make_io_type)


def k6_n10_arch() -> Arch:
    """K=6, N=10, I=33 soft-logic architecture, single wire type (length 1,
    bidirectional), buffered switches: the tests' fixture for the k6_N10
    cluster.  The published k6_N10_40nm routing architecture is
    ``k6_n10_40nm_arch``."""
    arch = Arch(
        name="k6_N10",
        K=6, N=10, I=33, io_capacity=8,
        segments=[SegmentInf(name="l1", length=1, frequency=1.0,
                             Rmetal=101.0, Cmetal=22.5e-15,
                             wire_switch=0, opin_switch=1)],
        switches=[
            SwitchInf(name="wire_mux", buffered=True, R=551.0,
                      Cin=7.7e-15, Cout=12.9e-15, Tdel=58e-12),
            SwitchInf(name="opin_buf", buffered=True, R=551.0,
                      Cin=7.7e-15, Cout=12.9e-15, Tdel=75e-12),
        ],
        Fc_out=0.1, Fc_in=0.15,
        ipin_switch=0,
        default_chan_width=40,
    )
    arch.block_types = [
        make_io_type(index=0, capacity=arch.io_capacity),
        make_clb_type(index=1, K=arch.K, N=arch.N, I=arch.I,
                      T_comb=261e-12, T_setup=66e-12, T_clk_to_q=124e-12),
    ]
    return arch


def k6_n10_40nm_arch(chan_width: int = 120) -> Arch:
    """The routing architecture of VTR 7.0's
    ``vtr_flow/arch/timing/k6_N10_40nm.xml``, every number as published
    (``tests/golden/k6_frac_n10_mem.xml:23-46`` carries the same
    lines): K=6, N=10, I=33, 8 pads a perimeter tile; ONE segment type,
    length 4, single-driver, Rmetal 101, Cmetal 22.5e-15, sb pattern
    ``1 1 1 1 1``, cb pattern ``1 1 1 1``, driven (wire and OPIN alike)
    through the one mux R=551, Tdel=58e-12, Cin=0.77e-15, Cout=4e-15;
    Fc_in 0.15, Fc_out 0.10; the input connection block T=7.247e-11,
    C=1.47e-15; cluster inputs equivalent, outputs not (each BLE drives
    its own pin).  Block timing is ``k6_n10_arch``'s.  The file asks for a
    Wilton switch block, Fs=3; the rr builder realises its own Fs=3 box
    (rr/graph.py "Unidir switch box") and says so in a warning."""
    arch = Arch(
        name="k6_N10_40nm",
        K=6, N=10, I=33, io_capacity=8,
        segments=[SegmentInf(name="l4", length=4, frequency=1.0,
                             Rmetal=101.0, Cmetal=22.5e-15,
                             wire_switch=0, opin_switch=0,
                             directionality="unidir",
                             sb=(1, 1, 1, 1, 1), cb=(1, 1, 1, 1))],
        switches=[
            SwitchInf(name="0", buffered=True, R=551.0,
                      Cin=0.77e-15, Cout=4e-15, Tdel=58e-12),
            SwitchInf(name="ipin_cblock", buffered=True, R=0.0,
                      Cin=1.47e-15, Cout=0.0, Tdel=7.247e-11),
        ],
        Fc_out=0.10, Fc_in=0.15,
        ipin_switch=1,
        default_chan_width=chan_width,
        sb_type="wilton", sb_fs=3,
    )
    arch.block_types = [
        make_io_type(index=0, capacity=arch.io_capacity),
        make_clb_type(index=1, K=arch.K, N=arch.N, I=arch.I,
                      T_comb=261e-12, T_setup=66e-12, T_clk_to_q=124e-12,
                      output_equivalent=False),
    ]
    return arch


def k6_frac_n10_mem32k_40nm_arch(chan_width: int = 120,
                                 mult_combinational: bool = False) -> Arch:
    """VTR 7.0's flagship heterogeneous architecture,
    ``vtr_flow/arch/timing/k6_frac_N10_mem32K_40nm.xml``, built ON
    ``k6_n10_40nm_arch``: the routing half (segment, mux, Fc, connection
    block, switch box) is that one definition, every block type at
    Fc_in 0.15 / Fc_out 0.10.  The blocks as published: ``io`` 8 a
    tile; ``clb`` N=10 fracturable logic elements (one 6-LUT or two
    5-LUTs on shared inputs, two flip-flops, TWO outputs), I=40
    equivalent inputs, O=20 outputs not equivalent, one clock;
    ``mult_36`` 4 rows tall, ``a[36]``, ``b[36]`` -> ``out[72]``,
    columns 4 + 8k; ``memory`` (32 Kb) 6 rows tall, ``addr1[15]``,
    ``addr2[15]``, ``data[64]``, ``we1``, ``we2`` -> ``out[64]``, one
    clock, columns 2 + 8k.  A hard block's pins are one class a pin
    (``make_hard_type``).  NOT as published (the configuration that
    runs this says so under ``assumed``): block timing (the cluster's is
    ``k6_n10_arch``'s, the hard blocks' is recalled from the file, not
    read from it); ``mult_36`` carries the clock pin every hard type
    of this repo has (its timing graph takes a hard block as
    registered); the packer fills an FLE with ONE LUT and drives the
    first of its two outputs (no pb tree: the two-mode pack is
    ``frac_arch``'s, at test size).

    ``mult_combinational=True`` builds ``mult_36`` AS PUBLISHED: no
    clock pin (144 pins), combinational, the pin-to-pin
    ``delay_constant`` by mode (a/b -> out 1.523e-9 in the 9x9 and
    18x18 modes, 1.93e-9 in 36x36, recalled from the file; a primitive
    that names no mode gets the 36x36 figure), so timing paths run
    THROUGH a multiplier (timing/graph.py).  The default keeps the
    registered stand-in the ``route_hetero`` configuration states under
    ``assumed`` and is measured on."""
    mult_timing = (dict(combinational=True, T_comb=1.93e-9,
                        mode_T_comb={"mult_9x9": 1.523e-9,
                                     "mult_18x18": 1.523e-9,
                                     "mult_36x36": 1.93e-9})
                   if mult_combinational else
                   dict(T_comb=1.523e-9, T_setup=66e-12,
                        T_clk_to_q=124e-12))
    arch = k6_n10_40nm_arch(chan_width)
    arch.name = "k6_frac_N10_mem32K_40nm"
    arch.I = 40
    arch.block_types = [
        make_io_type(index=0, capacity=arch.io_capacity),
        make_clb_type(index=1, K=arch.K, N=arch.N, I=arch.I,
                      T_comb=261e-12, T_setup=66e-12, T_clk_to_q=124e-12,
                      output_equivalent=False, outputs_per_ble=2),
        make_hard_type("mult_36", index=2, num_in=36 + 36, num_out=72,
                       height=4, **mult_timing),
        make_hard_type("memory", index=3, num_in=15 + 15 + 64 + 2,
                       num_out=64, height=6, T_comb=1.234e-9,
                       T_setup=509e-12, T_clk_to_q=1.234e-9),
    ]
    arch.column_types = [ColumnSpec("memory", start=2, repeat=8),
                         ColumnSpec("mult_36", start=4, repeat=8)]
    arch.hard_models = {"multiply": "mult_36", "dual_port_ram": "memory"}
    return arch


def k6_n10_mem_arch(addr_bits: int = 6, data_bits: int = 8,
                    mem_start: int = 4, mem_repeat: int = 6) -> Arch:
    """A TOY, kept for the tests that use it: k6_N10's length-1
    bidirectional fixture plus ONE made-up single-port RAM type of
    height 1 ('bram': addr + data-in + we, then data-out, then clk, at
    whatever widths the test asks), on periodic columns.  The published
    heterogeneous architecture is ``k6_frac_n10_mem32k_40nm_arch``.  The
    'spram' .subckt model maps onto 'bram'."""
    arch = k6_n10_arch()
    arch.name = "k6_N10_mem"
    num_in = addr_bits + data_bits + 1          # addr, din, we
    arch.block_types.append(make_hard_type(
        "bram", index=2, num_in=num_in, num_out=data_bits,
        T_comb=1.5e-9, T_setup=100e-12, T_clk_to_q=440e-12))
    arch.column_types = [ColumnSpec("bram", start=mem_start,
                                    repeat=mem_repeat)]
    arch.hard_models = {"spram": "bram"}
    return arch


def minimal_arch(K: int = 4, N: int = 2, I: int = 6,
                 io_capacity: int = 2, chan_width: int = 12) -> Arch:
    """Tiny architecture for tests: small CLBs so rr-graphs stay small."""
    arch = Arch(
        name="minimal",
        K=K, N=N, I=I, io_capacity=io_capacity,
        segments=[SegmentInf()],
        switches=[SwitchInf(), SwitchInf(name="opin_buf", Tdel=70e-12)],
        Fc_out=0.5, Fc_in=0.5,
        ipin_switch=0,
        default_chan_width=chan_width,
    )
    arch.block_types = [
        make_io_type(index=0, capacity=io_capacity),
        make_clb_type(index=1, K=K, N=N, I=I),
    ]
    return arch


def unidir_arch(K: int = 4, N: int = 2, I: int = 6,
                io_capacity: int = 2, chan_width: int = 12,
                length: int = 1) -> Arch:
    """Minimal arch with single-driver unidirectional wires (the modern
    VTR/Titan directionality, reference rr_graph.c:432-548
    UNI_DIRECTIONAL): even tracks run INC, odd DEC, wires are driven
    only at their start through the segment mux."""
    arch = minimal_arch(K=K, N=N, I=I, io_capacity=io_capacity,
                        chan_width=chan_width)
    arch.name = "minimal_unidir"
    arch.segments = [SegmentInf(name=f"l{length}", length=length,
                                directionality="unidir")]
    # unidir reaches fewer wires per pin position (starts only): keep
    # Fc generous so IO pads stay richly connected
    arch.Fc_out = 0.5
    arch.Fc_in = 0.5
    return arch


_FRAC_PB_XML = """
<pb_type name="clb">
  <input name="I" num_pins="{I}"/>
  <output name="O" num_pins="{O}"/>
  <clock name="clk" num_pins="1"/>
  <pb_type name="ble" num_pb="{N}">
    <input name="in" num_pins="10"/>
    <output name="out" num_pins="2"/>
    <clock name="clk" num_pins="1"/>
    <mode name="lut6">
      <pb_type name="lut6" blif_model=".names" num_pb="1">
        <input name="in" num_pins="6"/><output name="out" num_pins="1"/>
      </pb_type>
      <pb_type name="ff" blif_model=".latch" num_pb="1">
        <input name="D" num_pins="1"/><output name="Q" num_pins="1"/>
        <clock name="clk" num_pins="1"/>
      </pb_type>
      <interconnect>
        <direct name="d_in" input="ble.in[5:0]" output="lut6.in"/>
        <mux name="m_d" input="lut6.out ble.in[6]" output="ff.D"/>
        <mux name="m_o" input="lut6.out ff.Q" output="ble.out[0]"/>
        <direct name="d_c" input="ble.clk" output="ff.clk"/>
      </interconnect>
    </mode>
    <mode name="lut5x2">
      <pb_type name="lut5" blif_model=".names" num_pb="2">
        <input name="in" num_pins="5"/><output name="out" num_pins="1"/>
      </pb_type>
      <pb_type name="ff" blif_model=".latch" num_pb="2">
        <input name="D" num_pins="1"/><output name="Q" num_pins="1"/>
        <clock name="clk" num_pins="1"/>
      </pb_type>
      <interconnect>
        <direct name="d0" input="ble.in[4:0]" output="lut5[0].in"/>
        <direct name="d1" input="ble.in[9:5]" output="lut5[1].in"/>
        <mux name="m0" input="lut5[0].out ble.in[0]" output="ff[0].D"/>
        <mux name="m1" input="lut5[1].out ble.in[5]" output="ff[1].D"/>
        <mux name="o0" input="lut5[0].out ff[0].Q" output="ble.out[0]"/>
        <mux name="o1" input="lut5[1].out ff[1].Q" output="ble.out[1]"/>
        <complete name="dc" input="ble.clk" output="ff[0:1].clk"/>
      </interconnect>
    </mode>
  </pb_type>
  <interconnect>
    <complete name="xbar" input="clb.I ble[0:{NM1}].out" output="ble[0:{NM1}].in"/>
    <direct name="outs" input="ble[0:{NM1}].out" output="clb.O"/>
    <complete name="clks" input="clb.clk" output="ble[0:{NM1}].clk"/>
  </interconnect>
</pb_type>
"""


def frac_arch(N: int = 4, I: int = 20, chan_width: int = 14) -> Arch:
    """Fracturable-LUT multi-mode architecture: each of the N BLE slots
    runs as one 6-LUT (mode lut6) or two independent 5-LUTs (mode
    lut5x2), k6_frac-style.  The pb tree drives packing (mode choice +
    cluster_legality.c-style detail routing, pack/pb_pack.py); the flat
    K/N/I view drives the rr graph: I cluster inputs, 2N output pins
    (two per slot), K=6 for BLIF reading."""
    import xml.etree.ElementTree as ET

    from ..pack.pb_type import parse_pb_type

    arch = minimal_arch(K=6, N=2 * N, I=I, chan_width=chan_width)
    arch.name = f"frac_N{N}"
    xml = _FRAC_PB_XML.format(I=I, O=2 * N, N=N, NM1=N - 1)
    arch.pb_tree = parse_pb_type(ET.fromstring(xml))
    return arch
