"""tools/kernel_bundles.py reads the TPU compiler's final bundles: its parser
on a 42-bundle cut of the C51 megakernel's real dump (tree ca7e2b6, libtpu
0.0.34; tests/bundle_fixtures/): the entry, the grid loop's head and back
edge, every branch with its fallthrough, four bundles of the target critic's
matmuls and eight of the old projection's permutes. Line numbers are the
dump's own, so the phase counts are the whole kernel's."""

import os

import pytest

from distributed_ddpg_tpu.tools import kernel_bundles as kb

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bundle_fixtures")


@pytest.fixture(scope="module")
def sched():
    return kb.load_dump(FIXTURES)


def test_bundles_and_utilisation_rows_pair_up(sched):
    assert len(sched.bundles) == len(sched.rows) == 42
    assert sched.names == ["MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE", "VSTORE:SPILL", "SALU"]
    assert sched.capacity == [4, 3, 4, 1, 3, 3, 1, 1, 2]
    assert [b.addr for b in sched.bundles[:3]] == [0, 1, 2]
    assert [b.marker for b in sched.bundles].count("LB") == 1
    assert [b.marker for b in sched.bundles].count("PF") == 5


def test_regions_pair_each_branch_with_its_fallthrough(sched):
    # the body's guard, the k == 0 seed, c51_edge_mass's cond, the metric
    # block's seed and its accumulate: targets are in an older numbering, so
    # the pairing is by rank
    assert kb.regions(sched.bundles) == [
        (0x14A, 0x62BD), (0x162, 0x7CC), (0x3572, 0x35EE), (0x615A, 0x6161), (0x6163, 0x616B),
    ]


def test_phases_count_the_seed_and_an_update(sched):
    ph = kb.phases(sched.bundles)
    assert ph["loop_body"] == 0x62C2 - 0xEC + 1 == 25047
    assert ph["seed"] == 0x7CC - 0x162 == 1642
    assert ph["update"] == 23405
    assert ph["branched"] == [(0x3572, 124), (0x615A, 7), (0x6163, 8)]


def test_mxu_free_stretch_saturated_on_the_xlu_is_named(sched):
    stretches = kb.mxu_free_stretches(sched, min_len=4)
    assert [(a, b) for a, b, _ in stretches] == [(0, 12), (16, 24), (26, 42)]
    bound = [[n for n in ("XLU", "VALU") if kb.saturated(sched, m, n)] for _, _, m in stretches]
    assert bound == [[], ["XLU"], []]
    text = kb.report(sched, window=10, min_len=4)
    assert "the k == 0 seed 1642, an update 23405" in text
    assert text.count("SATURATED: XLU") == 1
    # the four matmul bundles fill the window they lie in
    assert kb.window_table(sched, 10)[1][1][0] == pytest.approx(1.6)


def test_parsers_skip_what_is_no_bundle():
    assert kb.parse_bundles("= control target key start\nLB: loop body\n\n") == []
    with pytest.raises(ValueError):
        kb.parse_utilization("nothing here\n")
    with pytest.raises(FileNotFoundError):
        kb.load_dump(os.path.dirname(FIXTURES) + "/lint_fixtures")


def test_a_loop_nested_in_the_grid_loop_is_counted_by_its_trip(sched):
    """The pixel crop's kernel loops over an image's rows inside a grid
    step; the compiler marks a nested loop's lines `>>`, and its back edge
    names an older number than its `LB` line's."""
    text = "\n".join([
        "     0   :  { %7 = vsyncpa [#allocation5], 0 }",
        "   0x1 LB: > { %s1 = sadd.s32 1, %s0 }",
        "   0x2   : > { %1 = sbr.rel (%p1) target bundleno = 9 (0x9), region = 16 }",
        "   0x3 LB: >> { %vm1 = vcmp.eq.s32.totalorder %v1, 1 }",
        "   0x4   : >> { %v2 = vsel %vm1, %v3, %v4 }",
        "   0x5   : >> { %2 = vst [vmem:[%s2] ss:$4 sm:$0xff] %v2  ;;  %3 = sbr.rel (!%p2) target bundleno = 4 (0x4), region = 100 }",
        "   0x6 PF: > { %s3 = sadd.s32 1, %s2 }",
        "   0x7   :  { %4 = sbr.rel (!%p3) target bundleno = 1 (0x1), region = 111 }",
    ])
    bundles = kb.parse_bundles(text)
    assert [b.addr for b in bundles] == list(range(8)) and [b.marker for b in bundles].count("LB") == 2
    assert kb.inner_loops(bundles) == [3]
    assert kb.phases(bundles)["loop_body"] == 7 and kb.phases(bundles)["loops"] == [3]
    assert kb.phases(sched.bundles)["loops"] == []  # the megakernel has none
