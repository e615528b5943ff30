"""The bundle reader of ``scripts/kernel_bundles.py`` on a hand-written
dump in the TPU compiler's final-bundles text form: a grid loop, a
register-tile loop inside it and a double-round loop inside that, whose
exit branches sit where the compiler puts them (the tile loop's exit
test in a bundle of the inner loop, the grid loop's backward branch
outside every loop).  No compiler runs here."""

import importlib.util
import os

import pytest

DUMP = """\
= control target key start
LB: loop body
= control target key end

     0   :  { %s1_s0 = smov 0 }
   0x1 LB: > { %v1_v0 = vld [vmem:[%s1_s0] sm:$0xff] }
   0x2 LB: >> { %v2_v1 = vld [vmem:[%s1_s0 + $0x8] sm:$0xff]  ;;  %s5_s1 = sadd.s32 1, %s4_s1 }
   0x3 LB: >>> { %v3_v2 = vadd.s32 %v2_v1, %v1_v0  ;;  %p6_p0 = scmp.ge.s32.totalorder %s7_s2, 6 /* loop exit test */ }
   0x4   : >>> { %v8_v3 = vxor.u32 %v3_v2, %v1_v0  ;;  %p9_p1 = scmp.ge.s32.totalorder (%p6_p0), %s5_s1, 8 /* loop exit test */  ;;  %10 = sbr.rel (!%p6_p0) target bundleno = 90 (0x5a), region = 7 }
   0x5   : >>> { %v11_v4 = vshll.u32 %v8_v3, 7 }
   0x6   : >> { %12 = vst [vmem:[%s1_s0] sm:$0xff] /*vst_source=*/%v11_v4  ;;  %13 = sbr.rel (!%p9_p1) target bundleno = 80 (0x50), region = 8 }
   0x7   : > { %v14_v5 = vsel /*vm=*/%vm1_vm0, /*on_true_vy=*/1, /*on_false_vx=*/%v11_v4  ;;  %p15_p2 = scmp.ge.s32.totalorder %s16_s3, 1026 /* loop exit test */ }
   0x8   :  { %17 = sbr.rel (!%p15_p2) target bundleno = 1 (0x1), region = 9 }
"""


@pytest.fixture(scope="module")
def kb():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "kernel_bundles.py")
    spec = importlib.util.spec_from_file_location("kernel_bundles", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def bundles(kb, tmp_path):
    p = tmp_path / "k-final_bundles.txt"
    p.write_text(DUMP)
    return kb.parse_bundles(str(p))


def test_loops_nest_like_brackets(kb, bundles):
    """Each body closes at the first unclaimed branch on a loop exit
    test after it opens, runs on over its depth's delay slots, and takes
    that test's bound as its trip count."""
    assert len(bundles) == 9
    assert kb.find_loops(bundles) == [(1, 8, 1, 1026), (2, 6, 2, 8),
                                      (3, 5, 3, 6)]


def test_loop_counts_and_grid_step(kb, bundles):
    """The double-round body holds 3 bundles and 3 VALU ops; one grid
    step runs its own 3 bundles and 8 tile passes of 2 bundles and 6
    double rounds of 3."""
    loops = kb.find_loops(bundles)
    assert kb.loop_stats(bundles, 3, 5) == {"bundles": 3, "vld": 0,
                                            "vst": 0, "valu": 3}
    assert kb.loop_stats(bundles, 2, 6)["vld"] == 1
    assert kb.loop_stats(bundles, 2, 6)["vst"] == 1
    assert kb.step_bundles(bundles, loops, 1, 8, 1) == 3 + 8 * (2 + 6 * 3)
