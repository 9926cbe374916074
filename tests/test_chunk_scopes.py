"""The chunk programs name their own parts (trace.CHUNK_SCOPES): the table
from instruction name to scope that `op_scopes` reads out of a compiled
module's text, the tables of the programs `_build_programs` selects for the
five families on both legs, the compile cache that must not answer a scoped
program with an unscoped one's executable, `chunk_ops.json` beside a
run's records, and the table's `scalars` (PR 46: the unfused arithmetic on a
`[]` shape in the scan's body; that it fell with the gradient norms' one
sum a net is held where a TPU compiler's text can be had, in
tests/test_ring_layout.py: XLA:CPU fuses scalar arithmetic and reads 0)."""

import contextlib
import json
import os

import jax
import numpy as np
import pytest

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.replay.device import DeviceReplay

OBS, ACT, B, K = 5, 2, 8, 8  # K past the scan's unroll of 4: the loop stays a loop

# A compiled module as `compiled.as_text()` prints it, cut to what the table
# reads: a fusion with its body, a `while` with condition and body, a
# `conditional` with two branches, an all-reduce with its reducer, a
# tuple-typed instruction, a copy under no bracket, and a fusion whose body
# was traced under three brackets.
HLO = """\
HloModule jit_sample_chunk_fn, is_scheduled=true, entry_computation_layout={(f32[64,15]{1,0})->f32[4,4]{1,0}}

%region_1.0 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0), metadata={op_name="update/while/body/closed_call/critic/psum"}
  %b.1 = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%a.1, %b.1), metadata={op_name="critic/add"}
}

%fused_computation (param_0: f32[64,15], param_1: s32[2,8]) -> f32[2,8,15] {
  %param_0 = f32[64,15]{1,0} parameter(0)
  %param_1 = s32[2,8]{1,0} parameter(1)
  ROOT %gather.9 = f32[2,8,15]{2,1,0} gather(%param_0, %param_1), offset_dims={2}, metadata={op_name="jit(sample_chunk_fn)/gather/gather"}
}

%fused_computation.3 (param_0.3: f32[4,4]) -> f32[4,4] {
  %param_0.3 = f32[4,4]{1,0} parameter(0)
  %convolution.5 = f32[4,4]{1,0} convolution(%param_0.3, %param_0.3), dim_labels=bf_io->bf, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/transpose(jvp())/dot_general"}
  %subtract.5 = f32[4,4]{1,0} subtract(%convolution.5, %param_0.3), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/optim/sub"}
  ROOT %add.6 = f32[4,4]{1,0} add(%subtract.5, %param_0.3), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/polyak/add"}
}

%fused_computation.4 () -> s32[2,8] {
  %iota.1 = s32[2,8]{1,0} iota(), iota_dimension=0, metadata={op_name="jit(sample_chunk_fn)/draw/jit(_randint)/iota"}
  %add.1 = s32[2,8]{1,0} add(%iota.1, %iota.1), metadata={op_name="jit(sample_chunk_fn)/gather/add"}
  ROOT %xor.2 = s32[2,8]{1,0} xor(%iota.1, %iota.1), metadata={op_name="jit(sample_chunk_fn)/draw/jit(_randint)/xor"}
}

%branch_0 (p.0: (f32[4,4])) -> (f32[4,4]) {
  ROOT %p.0 = (f32[4,4]{1,0}) parameter(0)
}

%branch_1 (p.1: (f32[4,4])) -> (f32[4,4]) {
  %p.1 = (f32[4,4]{1,0}) parameter(0)
  %gte.5 = f32[4,4]{1,0} get-tuple-element(%p.1), index=0
  %multiply.7 = f32[4,4]{1,0} multiply(%gte.5, %gte.5), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/cond/branch_1_fun/actor/mul"}
  ROOT %tuple.8 = (f32[4,4]{1,0}) tuple(%multiply.7)
}

%body.2 (w.1: (s32[], f32[4,4], f32[2,8,15])) -> (s32[], f32[4,4], f32[2,8,15]) {
  %w.1 = (s32[], f32[4,4]{1,0}, f32[2,8,15]{2,1,0}) parameter(0)
  %dynamic-slice.4 = f32[8,15]{1,0} dynamic-slice(%w.1), dynamic_slice_sizes={1,8,15}, metadata={op_name="jit(sample_chunk_fn)/update/while/body/dynamic_slice"}
  %convolution_add_fusion.1 = f32[4,4]{1,0} fusion(%dynamic-slice.4), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/dot_general"}
  %pad_clamp_fusion.8 = s32[8]{0} fusion(%dynamic-slice.4), kind=kLoop, calls=%fused_computation.6, metadata={op_name="critic/jvp()/gather"}
  %all-reduce.330 = f32[4,4]{1,0} all-reduce(%convolution_add_fusion.1), channel_id=1, replica_groups={{0,1}}, to_apply=%region_1.0, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/psum"}
  %conditional.6 = (f32[4,4]{1,0}) conditional(%all-reduce.330, %w.1, %w.1), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/cond"}
  %divide_subtract_fusion.12 = f32[4,4]{1,0} fusion(%conditional.6), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/optim/sub"}
  %multiply_add_fusion.227 = f32[4,4]{1,0} fusion(%divide_subtract_fusion.12), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/polyak/add"}
  ROOT %tuple.9 = (s32[], f32[4,4]{1,0}, f32[2,8,15]{2,1,0}) tuple(%w.1, %multiply_add_fusion.227, %w.1)
}

%cond.2 (w.2: (s32[], f32[4,4], f32[2,8,15])) -> pred[] {
  %w.2 = (s32[], f32[4,4]{1,0}, f32[2,8,15]{2,1,0}) parameter(0)
  ROOT %compare.1 = pred[] compare(%w.2, %w.2), direction=LT, metadata={op_name="jit(sample_chunk_fn)/update/while/cond/lt"}
}

ENTRY %main.1 (Arg_0.1: f32[64,15]) -> f32[4,4] {
  %Arg_0.1 = f32[64,15]{1,0} parameter(0)
  %fusion.38 = s32[2,8]{1,0} fusion(), kind=kLoop, calls=%fused_computation.4
  %fusion.39 = s32[2,8]{1,0} fusion(%fusion.38), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(sample_chunk_fn)/draw/jit(_randint)/add"}
  %fusion = f32[2,8,15]{2,1,0} fusion(%Arg_0.1, %fusion.39), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(sample_chunk_fn)/gather/gather"}
  %slice.29 = f32[2,8,5]{2,1,0} slice(%fusion), slice={[0:2], [0:8], [0:5]}, metadata={op_name="jit(sample_chunk_fn)/cut/slice;jit(sample_chunk_fn)/cut/squeeze"}
  %copy.63 = f32[4,4]{0,1} copy(%Arg_0.1)
  %tuple.2 = (s32[], f32[4,4]{1,0}, f32[2,8,15]{2,1,0}) tuple(%fusion.39, %copy.63, %fusion)
  %while.3 = (s32[], f32[4,4]{1,0:T(8,128)}, f32[2,8,15]{2,1,0}) while(%tuple.2), condition=%cond.2, body=%body.2, metadata={op_name="jit(sample_chunk_fn)/update/while"}
  %get-tuple-element.7 = f32[4,4]{1,0} get-tuple-element(%while.3), index=1
  ROOT %reduce.8 = f32[4,4]{1,0} reduce(%get-tuple-element.7, %get-tuple-element.7), dimensions={}, to_apply=%region_1.0, metadata={op_name="jit(sample_chunk_fn)/metrics/reduce_sum"}
}
"""


def test_op_scopes_reads_fusions_loop_bodies_branches_and_collectives():
    assert trace.op_scopes(HLO) == {
        "fusion.38": "draw",  # nameless itself: the scope of most of its body
        "fusion.39": "draw",
        "fusion": "gather",  # the primitive's own name is no bracket
        "slice.29": "cut",  # two instructions made one: `a;b`, the first speaks
        "while.3": "update",
        "dynamic-slice.4": "update",
        "convolution_add_fusion.1": "update/critic",
        "pad_clamp_fusion.8": "update/critic",  # a path cut short of the loop's bracket
        "all-reduce.330": "collective",
        "conditional.6": "update",
        "multiply.7": "update/actor",  # inside a branch
        "divide_subtract_fusion.12": "update/optim",
        "multiply_add_fusion.227": "update/polyak",
        "compare.1": "update",  # the loop's condition
        "reduce.8": "metrics",
    }  # copy.63 under no bracket; fusion bodies and the reducer inlined; parameters and tuples never run
    table = trace.chunk_ops_table(HLO)
    assert table["module"] == "jit_sample_chunk_fn"
    assert table["scopes"] == list(trace.CHUNK_SCOPES)
    assert table["served"] == {"all-reduce.330": "update/critic"}
    assert table["loops"] == ["while.3"]
    # one operation, one scope, its root's: what else it holds is said apart
    assert table["fused"] == {
        "multiply_add_fusion.227": ["update/critic", "update/optim"], "fusion.38": ["gather"],
    }
    assert table["ops"] == trace.op_scopes(HLO)
    assert set(table["ops"].values()) <= set(trace.CHUNK_SCOPES) | {trace.COLLECTIVE}


def test_the_table_counts_the_loop_bodys_unfused_scalar_arithmetic():
    """`scalars`: elementwise instructions on a `[]` shape that stand in a
    `while` body by themselves, one trip. Not a fusion's (one instruction,
    whatever it holds), not an array's, not a copy or a slice, not what a
    `conditional` under the body runs in its branches, not the entry's."""
    assert trace.chunk_ops_table(HLO)["scalars"] == 0
    body = '  %dynamic-slice.4 = f32[8,15]{1,0} dynamic-slice('
    scalars = (
        '  %add.77 = s32[] add(%w.1, %w.1), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/optim/add"}\n'
        '  %convert.7 = f32[] convert(%add.77), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/optim/convert_element_type"}\n'
        '  %power.7 = f32[]{:T(128)} power(%convert.7, %convert.7), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/optim/pow"}\n'
        '  %sqrt.7 = f32[] sqrt(%power.7), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/sqrt"}\n'
        '  %copy.7 = f32[] copy(%sqrt.7)\n'
        '  %slice.7 = f32[1]{0} slice(%w.1), slice={[0:1]}\n'
        '  %multiply.70 = f32[4,4]{1,0} multiply(%w.1, %w.1), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/mul"}\n'
    )
    text = HLO.replace(body, scalars + body)
    text = text.replace(  # one in a branch, one in the entry computation
        "  %multiply.7 = f32[4,4]{1,0} multiply(", "  %negate.70 = f32[] negate(%gte.5)\n  %multiply.7 = f32[4,4]{1,0} multiply("
    ).replace("  %copy.63 = ", "  %add.78 = f32[] add(%Arg_0.1, %Arg_0.1)\n  %copy.63 = ")
    table = trace.chunk_ops_table(text)
    assert table["scalars"] == 4 and isinstance(table["scalars"], int)
    assert table["loops"] == ["while.3"]
    # the instructions are operations of their own all the same, each under its scope
    assert table["ops"]["power.7"] == "update/optim" and table["ops"]["negate.70"] == "update"
    assert trace.chunk_ops_table(ASYNC_HLO)["scalars"] == 0


def test_the_table_counts_the_loop_bodys_relayouts_and_their_bytes():
    """`copies`: what a `while` body runs to move an array and compute
    nothing, one trip: `copy`, `transpose` and `reshape` instructions, a
    fusion whose root is one, a fusion that holds nothing else; each with
    its result's bytes, the type's width times the dimensions. Not the TPU's
    `copy-start` / `copy-done` (a move between memory spaces in one layout),
    not a `bitcast`, not a fusion that computes under another root, not what
    a `conditional`'s branch or the entry computation copies."""
    assert trace.chunk_ops_table(HLO)["copies"] == {"count": 0, "bytes": 0}
    fusions = (
        '%fused_computation.9 (param_0.9: f32[4,4]) -> f32[4,4] {\n'
        '  %param_0.9 = f32[4,4]{1,0} parameter(0)\n'
        '  %add.9 = f32[4,4]{1,0} add(%param_0.9, %param_0.9)\n'
        '  ROOT %copy.90 = f32[4,4]{0,1} copy(%add.9)\n'
        '}\n\n'
        '%fused_computation.10 (param_0.10: s32[2,8]) -> s32[16] {\n'
        '  %param_0.10 = s32[2,8]{1,0} parameter(0)\n'
        '  %copy.91 = s32[2,8]{0,1} copy(%param_0.10)\n'
        '  ROOT %bitcast.91 = s32[16]{0} bitcast(%copy.91)\n'
        '}\n\n'
    )
    body = '  %dynamic-slice.4 = f32[8,15]{1,0} dynamic-slice('
    moves = (
        '  %copy.9 = bf16[8,4,3,3]{0,3,2,1:T(8,128)(2,1)S(1)} copy(%w.1)\n'  # 8*4*3*3 * 2
        '  %transpose.9 = f32[15,8]{1,0} transpose(%w.1), dimensions={1,0}\n'  # 15*8 * 4
        '  %reshape.9 = pred[120]{0} reshape(%w.1)\n'  # 120 * 1
        '  %copy_fusion.9 = f32[4,4]{0,1} fusion(%w.1), kind=kLoop, calls=%fused_computation.9\n'  # 16 * 4
        '  %copy_bitcast_fusion.9 = s32[16]{0} fusion(%w.1), kind=kLoop, calls=%fused_computation.10\n'  # 16 * 4
        '  %copy-start.9 = (f32[4,4]{1,0:S(1)}, f32[4,4]{1,0}, u32[]{:S(2)}) copy-start(%w.1)\n'
        '  %copy-done.9 = f32[4,4]{1,0:S(1)} copy-done(%copy-start.9)\n'
        '  %bitcast.9 = f32[120]{0} bitcast(%w.1)\n'
    )
    text = HLO.replace("%body.2 (w.1:", fusions + "%body.2 (w.1:").replace(body, moves + body)
    text = text.replace(  # one in a branch; the entry computation has its copy.63
        "  %multiply.7 = f32[4,4]{1,0} multiply(", "  %copy.70 = f32[4,4]{0,1} copy(%gte.5)\n  %multiply.7 = f32[4,4]{1,0} multiply("
    )
    table = trace.chunk_ops_table(text)
    assert table["copies"] == {"count": 5, "bytes": 576 + 480 + 120 + 64 + 64}
    assert table["loops"] == ["while.3"] and table["scalars"] == 0
    assert trace.chunk_ops_table(ASYNC_HLO)["copies"] == {"count": 0, "bytes": 0}


def test_the_table_counts_the_loop_bodys_gathers():
    """`gathers`: the `gather` instructions a `while` body issues and its
    fusions that hold one, whatever their kind (the TPU's compiler makes a
    `kCustom` fusion of a gather, its body's root), one trip. Not the entry
    computation's (the ring's rows are gathered in front of the loop: the
    HLO's `%fusion`), not what a `conditional`'s branch gathers, not a
    `dynamic-slice`, not an `all-gather`."""
    assert trace.chunk_ops_table(HLO)["gathers"] == 0
    fusions = (
        '%fused_computation.11 (param_0.11: pred[11,11], param_1.11: s32[88]) -> pred[8,11,11] {\n'
        '  %param_0.11 = pred[11,11]{1,0} parameter(0)\n'
        '  %param_1.11 = s32[88]{0} parameter(1)\n'
        '  ROOT %gather.11 = pred[8,11,11]{2,1,0} gather(%param_0.11, %param_1.11), offset_dims={2}, metadata={op_name="critic/jvp()/gather"}\n'
        '}\n\n'
        '%fused_computation.12 (param_0.12: f32[64,15], param_1.12: s32[8]) -> f32[8,15] {\n'
        '  %param_0.12 = f32[64,15]{1,0} parameter(0)\n'
        '  %param_1.12 = s32[8]{0} parameter(1)\n'
        '  %gather.12 = f32[8,15]{1,0} gather(%param_0.12, %param_1.12), offset_dims={1}\n'
        '  ROOT %negate.12 = f32[8,15]{1,0} negate(%gather.12)\n'
        '}\n\n'
    )
    body = '  %dynamic-slice.4 = f32[8,15]{1,0} dynamic-slice('
    gathers = (
        '  %fusion.1208 = pred[8,11,11]{2,1,0} fusion(%w.1, %w.1), kind=kCustom, calls=%fused_computation.11, metadata={op_name="critic/jvp()/gather"}\n'
        '  %gather_negate_fusion = f32[8,15]{1,0} fusion(%w.1, %w.1), kind=kLoop, calls=%fused_computation.12\n'
        '  %gather.13 = f32[8,15]{1,0} gather(%w.1, %w.1), offset_dims={1}\n'
        '  %all-gather.13 = f32[8,15]{1,0} all-gather(%w.1), dimensions={0}\n'
    )
    text = HLO.replace("%body.2 (w.1:", fusions + "%body.2 (w.1:").replace(body, gathers + body)
    text = text.replace(  # one in a branch; the entry computation has its `%fusion`
        "  %multiply.7 = f32[4,4]{1,0} multiply(", "  %gather.70 = f32[4,4]{1,0} gather(%gte.5, %gte.5), offset_dims={1}\n  %multiply.7 = f32[4,4]{1,0} multiply("
    )
    table = trace.chunk_ops_table(text)
    assert table["gathers"] == 3 and isinstance(table["gathers"], int)
    assert table["loops"] == ["while.3"] and table["copies"] == {"count": 0, "bytes": 0}
    assert table["ops"]["fusion.1208"] == "update/critic"  # an operation of the critic's all the same
    assert trace.chunk_ops_table(ASYNC_HLO)["gathers"] == 0


@pytest.mark.parametrize("opcode", [
    "all-reduce", "all-reduce-start", "all-reduce-done", "all-gather", "all-gather-start",
    "reduce-scatter", "collective-permute", "collective-permute-done", "all-to-all",
])
def test_every_collective_opcode_reads_collective_whatever_it_served(opcode):
    text = HLO.replace(" all-reduce(", f" {opcode}(")
    assert trace.op_scopes(text)["all-reduce.330"] == trace.COLLECTIVE
    assert trace.chunk_ops_table(text)["served"]["all-reduce.330"] == "update/critic"


# Both forms of an asynchronous collective, as libtpu 0.0.34 writes them for
# the x4 scan chunk (PERF.md §5) and as XLA's other backends do: an
# `all-reduce-start` / `-done` pair; and the TPU's, an all-reduce cut into
# steps under the opcode `fusion`: `async-collective-start` and `-done` hold
# a step and the compiler's glue and nothing that computes, the step between
# them rides the fusion of a matmul the reduce does not feed. Beside them one
# plain all-reduce, which holds the core until it has landed.
ASYNC_HLO = """\
HloModule jit_sample_chunk_fn, is_scheduled=true

%add.3 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.4 = f32[] add(%a.1, %b.1)
}

%fused_computation.20 (param_0.1: f32[64]) -> (f32[64], u32[]) {
  %param_0.1 = f32[64]{0} parameter(0)
  %all-reduce.39 = f32[64]{0} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add.3, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/psum"}
  ROOT %custom-call.10 = (f32[64]{0}, u32[]) custom-call(%param_0.1, %all-reduce.39), custom_call_target="ContinuationStart"
}

%async_collective_fusion.7 (param_0.2: f32[64], param_1.2: f32[4,4]) -> (f32[4,4], f32[64]) {
  %param_0.2 = f32[64]{0} parameter(0)
  %param_1.2 = f32[4,4]{1,0} parameter(1)
  %convolution.9 = f32[4,4]{1,0} convolution(%param_1.2, %param_1.2), dim_labels=bf_io->bf, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/actor/jvp()/dot_general"}
  %all-reduce.40 = f32[64]{0} all-reduce(%param_0.2), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add.3, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/psum"}
  ROOT %tuple.5 = (f32[4,4]{1,0}, f32[64]{0}) tuple(%convolution.9, %all-reduce.40)
}

%fused_computation.21 (param_0.3: f32[64], param_1.3: u32[]) -> f32[64] {
  %param_0.3 = f32[64]{0} parameter(0)
  %param_1.3 = u32[] parameter(1)
  %all-reduce.41 = f32[64]{0} all-reduce(%param_0.3), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add.3, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/psum"}
  ROOT %custom-call.11 = f32[64]{0} custom-call(%param_0.3, %all-reduce.41, %param_1.3), custom_call_target="ContinuationDone"
}

%body.2 (w.1: (f32[64], f32[4,4])) -> (f32[64], f32[4,4]) {
  %w.1 = (f32[64]{0}, f32[4,4]{1,0}) parameter(0)
  %g.0 = f32[64]{0} get-tuple-element(%w.1), index=0
  %g.1 = f32[4,4]{1,0} get-tuple-element(%w.1), index=1
  %async-collective-start = (f32[64]{0}, u32[]) fusion(%g.0), kind=kCustom, calls=%fused_computation.20
  %gte.1 = f32[64]{0} get-tuple-element(%async-collective-start), index=0
  %fusion.7 = (f32[4,4]{1,0}, f32[64]{0}) fusion(%gte.1, %g.1), kind=kOutput, calls=%async_collective_fusion.7, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/actor/jvp()/dot_general"}
  %gte.2 = f32[64]{0} get-tuple-element(%fusion.7), index=1
  %gte.3 = u32[] get-tuple-element(%async-collective-start), index=1
  %async-collective-done = f32[64]{0} fusion(%gte.2, %gte.3), kind=kCustom, calls=%fused_computation.21, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/critic/psum"}
  %all-reduce-start.1 = f32[64]{0} all-reduce-start(%async-collective-done), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add.3, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/actor/psum"}
  %negate.1 = f32[64]{0} negate(%async-collective-done), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/optim/neg"}
  %all-reduce-done.1 = f32[64]{0} all-reduce-done(%all-reduce-start.1), metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/actor/psum"}
  %all-reduce.5 = f32[64]{0} all-reduce(%negate.1), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add.3, metadata={op_name="jit(sample_chunk_fn)/update/while/body/closed_call/psum"}
  %gte.4 = f32[4,4]{1,0} get-tuple-element(%fusion.7), index=0
  ROOT %tuple.9 = (f32[64]{0}, f32[4,4]{1,0}) tuple(%all-reduce.5, %gte.4)
}

%cond.2 (w.2: (f32[64], f32[4,4])) -> pred[] {
  %w.2 = (f32[64]{0}, f32[4,4]{1,0}) parameter(0)
  ROOT %constant.1 = pred[] constant(true)
}

ENTRY %main.1 (Arg_0.1: (f32[64], f32[4,4])) -> (f32[64], f32[4,4]) {
  %Arg_0.1 = (f32[64]{0}, f32[4,4]{1,0}) parameter(0)
  ROOT %while.3 = (f32[64]{0}, f32[4,4]{1,0}) while(%Arg_0.1), condition=%cond.2, body=%body.2, metadata={op_name="jit(sample_chunk_fn)/update/while"}
}
"""


def test_the_table_tells_asynchronous_collectives_from_those_that_hold_the_core():
    table = trace.chunk_ops_table(ASYNC_HLO)
    # a start, a done and a plain all-reduce are collectives and nothing else
    # (what `chunk.collective_ms` reads: the time the wire holds the core);
    # the fusion that carries a step between them is the matmul it is
    assert table["ops"] == {
        "while.3": "update",
        "async-collective-start": "collective",
        "fusion.7": "update/actor",
        "async-collective-done": "collective",
        "all-reduce-start.1": "collective",
        "negate.1": "update/optim",
        "all-reduce-done.1": "collective",
        "all-reduce.5": "collective",
    }
    # `served` keeps what each was for: a nameless start takes its reduce's scope
    assert table["served"] == {
        "async-collective-start": "update/critic",
        "async-collective-done": "update/critic",
        "all-reduce-start.1": "update/actor",
        "all-reduce-done.1": "update/actor",
        "all-reduce.5": "update",
    }
    assert table["asynchronous"] == {
        "async-collective-start": "update/critic",
        "fusion.7": "update/actor",
        "async-collective-done": "update/critic",
        "all-reduce-start.1": "update/actor",
        "all-reduce-done.1": "update/actor",
    }
    assert table["fused"]["fusion.7"] == ["update/critic"]  # the step it carries
    # a program of plain all-reduces has none
    assert trace.chunk_ops_table(HLO)["asynchronous"] == {}
    assert set(trace.chunk_ops_table(HLO)["served"]) == {"all-reduce.330"}
    json.dumps(table)


def test_a_bracket_takes_only_the_vocabularys_words():
    with pytest.raises(ValueError, match="CHUNK_SCOPES"):
        trace.device_scope("sample")
    with trace.device_scope("update"), trace.device_scope("optim"):
        pass
    assert {w for s in trace.CHUNK_SCOPES for w in s.split("/")} == trace._SCOPE_WORDS


# --- the programs `_build_programs` selects ---

FAMILIES = {
    "ddpg": dict(),
    "td3": dict(twin_critic=True, policy_delay=2, target_noise=0.2),
    "c51": dict(distributional=True, num_atoms=11, v_min=-5.0, v_max=5.0),
    "sac": dict(sac=True),
    "redq": dict(sac=True, critic_ensemble=4, target_subset=2, policy_delay=2),
}
DRAWS_NOISE = {"td3", "sac", "redq"}


def launched(family, leg, devices=1):
    """A learner of `family` on `leg` after one launch through the public
    path, on a ring it filled."""
    cfg = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=B, seed=5,
        fused_chunk="on" if leg == "kernel" else "off", **FAMILIES[family],
    )
    mesh = mesh_lib.make_mesh(devices, 1, devices=jax.devices()[:devices])
    learner = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=K)
    assert learner.fused_chunk_active == (leg == "kernel")
    assert learner.chunk_ops() is None  # nothing launched yet
    replay = DeviceReplay(capacity=64, obs_dim=OBS, act_dim=ACT, mesh=mesh, block_size=64)
    replay.add_packed(
        np.random.default_rng(0).standard_normal((64, 2 * OBS + ACT + 3)).astype(np.float32)
    )
    learner.run_sample_chunk(replay)
    return learner


def backend_compiles(fn):
    """(fn(), the backend compiles JAX reported while it ran): a build and a
    load from the persistent cache both report one."""
    import jax.monitoring as monitoring

    from distributed_ddpg_tpu.metrics import CompileCounter

    seen = []

    def listener(event, seconds, **kw):
        if event == CompileCounter.BUILD:
            seen.append(seconds)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        return fn(), len(seen)
    finally:
        monitoring.unregister_event_duration_listener(listener)


def lacking(text, table):
    """Of the entry computation's instructions that do work: the share the
    table lacks though JAX named them (an `op_name`: something the program
    wrote and no bracket holds), and the opcodes of the nameless rest."""
    entry = {
        name: opcode for name, opcode, _, in_entry in trace._instructions(text)[0]
        if in_entry and opcode != "bitcast"
    }
    lines = {
        m.group(1): m.group(2)
        for m in map(trace._INSTRUCTION.match, text.splitlines()) if m
    }
    lacks = [name for name in entry if name not in table["ops"]]
    named = [name for name in lacks if "op_name=" in lines[name]]
    nameless = {entry[name] for name in lacks if name not in named}
    return len(named) / len(entry), nameless


@pytest.mark.parametrize("family,leg", [
    (f, "scan") for f in FAMILIES
] + [(f, "kernel") for f in FAMILIES if f != "redq"])  # an ensemble has no kernel branch
def test_the_selected_program_carries_the_vocabulary(family, leg):
    learner = launched(family, leg)
    # the executable is asked back from JAX's in-memory caches: nothing
    # compiles, nothing is loaded, so the table costs a finished run no set-up
    text, compiles = backend_compiles(learner.chunk_hlo)
    assert compiles == 0
    table = learner.chunk_ops()
    assert table["module"].startswith(
        "jit_fused_sample_chunk_fn" if leg == "kernel" else "jit_sample_chunk_fn"
    )
    found = set(table["ops"].values())
    want = {"draw", "gather", "cut", "update"}
    if family in DRAWS_NOISE:
        want.add("noise")
    if leg == "scan":
        want |= {"update/critic", "update/actor", "update/optim", "update/polyak", "metrics"}
        # the scan is a loop, and the loop itself reads `update`
        assert any(table["ops"].get(loop) == "update" for loop in table["loops"])
    else:
        # interpret mode: the kernel's body is ordinary instructions here,
        # all of them under the one bracket round the pallas_call
        assert not {s for s in found if s.startswith("update/")}
    assert want <= found, sorted(want - found)
    assert found <= set(trace.CHUNK_SCOPES)  # one chip: no collective
    assert not table["served"]
    # What the table lacks of the entry computation: under a fiftieth of it
    # is the program's own (the state's counters stepped behind the
    # kernel); the rest is the compiler's, copies of the state round the
    # loop and fusions of them that carry no metadata at all.
    named_share, nameless = lacking(text, table)
    assert named_share < 0.02 and nameless <= {"copy", "fusion", "call"}
    json.dumps(table)  # what train() writes


def test_on_a_data_mesh_the_scan_programs_all_reduces_read_collective():
    table = launched("sac", "scan", devices=2).chunk_ops()
    collectives = [name for name, scope in table["ops"].items() if scope == trace.COLLECTIVE]
    assert collectives and set(collectives) == set(table["served"])
    # each keeps the scope it served: a gradient's half of an update (XLA
    # may combine the two halves' all-reduces under one's name)
    assert set(table["served"].values()) <= {"update/critic", "update/actor"}


def test_the_run_fact_counts_the_launched_executables_collectives():
    """`chunk_collectives` (ShardedLearner.chunk_collectives, train.run_facts):
    null on one device and before a launch; on a 4x1 mesh the instructions
    the executable that ran holds and how many of them are asynchronous:
    none here, XLA:CPU being handed no option and writing plain all-reduces."""
    assert launched("sac", "scan").chunk_collectives() is None
    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=B, seed=5, fused_chunk="off", sac=True)
    mesh = mesh_lib.make_mesh(4, 1, devices=jax.devices()[:4])
    assert ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=K).chunk_collectives() is None
    learner = launched("sac", "scan", devices=4)
    counts = learner.chunk_collectives()
    assert counts == {"instructions": len(learner.chunk_ops()["served"]), "asynchronous": 0}
    assert counts["instructions"] >= 1
    learner._build_programs()  # a rebuilt program (LR backoff) has not run yet
    assert learner.chunk_collectives() is None


def test_the_run_fact_counts_the_launched_scan_bodys_scalars():
    """`chunk_body_scalars` (ShardedLearner.chunk_body_scalars,
    train.run_facts): the table's `scalars` of the executable that ran, an
    integer; null before a launch and on the kernel leg, which scans
    nothing."""
    learner = launched("sac", "scan")
    count = learner.chunk_body_scalars()
    assert isinstance(count, int) and count == learner.chunk_ops()["scalars"] >= 0
    learner._build_programs()
    assert learner.chunk_body_scalars() is None
    kernel = launched("sac", "kernel")
    assert kernel.chunk_body_scalars() is None and isinstance(kernel.chunk_ops()["scalars"], int)


def test_the_run_fact_counts_the_launched_scan_bodys_relayouts():
    """`chunk_body_copies` (ShardedLearner.chunk_body_copies,
    train.run_facts): the table's `copies` of the executable that ran, a
    count and its bytes; null where `chunk_body_scalars` is."""
    learner = launched("sac", "scan")
    copies = learner.chunk_body_copies()
    assert copies == learner.chunk_ops()["copies"] and set(copies) == {"count", "bytes"}
    assert all(isinstance(v, int) and v >= 0 for v in copies.values())
    assert (copies["count"] == 0) == (copies["bytes"] == 0)
    learner._build_programs()
    assert learner.chunk_body_copies() is None
    assert launched("sac", "kernel").chunk_body_copies() is None


@pytest.mark.parametrize("family", ["sac", "c51"])
def test_the_run_fact_counts_the_launched_scan_bodys_gathers(family):
    """`chunk_body_gathers` (ShardedLearner.chunk_body_gathers,
    train.run_facts): the table's `gathers` of the executable that ran; null
    where `chunk_body_scalars` is. 0 in SAC's body and, since PR 52, in the
    categorical critic's: `ops/losses.categorical_projection` indexes no
    table (its gather form read 8 here, two an unrolled update). The ring's
    rows are gathered in front of the loop."""
    learner = launched(family, "scan")
    assert learner.chunk_body_gathers() == learner.chunk_ops()["gathers"] == 0
    assert "gather" in set(learner.chunk_ops()["ops"].values())  # the ring's own, under its scope
    learner._build_programs()
    assert learner.chunk_body_gathers() is None
    if family == "sac":
        assert launched(family, "kernel").chunk_body_gathers() is None


# --- the compile cache must answer with the executable of THIS source ---


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compile cache of the test's own, taking every program."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_compilation_cache_include_metadata_in_key")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield tmp_path / "cache"
    for n, v in before.items():
        jax.config.update(n, v)
    cc.reset_cache()
    jax.clear_caches()


@pytest.mark.parametrize("metadata_in_key", [True, False])
def test_a_cache_filled_without_scopes_does_not_answer_the_scoped_program(
    cache_dir, monkeypatch, metadata_in_key
):
    """JAX leaves metadata out of the persistent cache's key by default: an
    entry compiled before the brackets existed is then loaded for the
    bracketed program, and its text says nothing of them (the second case
    shows the trap). `parallel/mesh.py` puts metadata into the key, so the
    table describes the executable that ran (the first)."""
    assert jax.config.jax_compilation_cache_include_metadata_in_key  # parallel/mesh.py set it
    jax.config.update("jax_compilation_cache_include_metadata_in_key", metadata_in_key)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        jax.clear_caches()
        assert launched("ddpg", "scan").chunk_ops()["ops"] == {}
    filled = len(os.listdir(cache_dir))
    assert filled > 0
    jax.clear_caches()  # a new process: nothing in memory, the directory as it was left
    table = launched("ddpg", "scan").chunk_ops()
    if metadata_in_key:
        assert {"draw", "gather", "cut", "update", "update/optim"} <= set(table["ops"].values())
        assert len(os.listdir(cache_dir)) > filled  # compiled anew, under another key
    else:
        assert table["ops"] == {}  # the unscoped executable, loaded under the same key


# --- train() writes the table beside its records ---


def run_train(log_path=None, trace_dir=None):
    from distributed_ddpg_tpu.train import train

    flags = [
        "--backend=jax_tpu", "--env_id=Pendulum-v1", "--num_actors=2", "--total_env_steps=1200",
        "--replay_min_size=300", "--eval_every=0", "--actor_hidden=16,16", "--critic_hidden=16,16",
        "--replay_capacity=4096", "--batch_size=16", "--learner_chunk=8",
    ]
    if log_path:
        flags.append(f"--log_path={log_path}")
    if trace_dir:
        flags.append(f"--trace_dir={trace_dir}")
    return train(DDPGConfig.from_flags(flags))


def test_train_writes_chunk_ops_beside_its_records_and_names_it(tmp_path):
    log = tmp_path / "run" / "records.jsonl"
    log.parent.mkdir()
    summary = run_train(log, tmp_path / "tr")
    assert summary["learner_steps"] > 0 and summary["log_path"] == str(log)
    assert summary["chunk_ops_path"] == str(log.parent / trace.CHUNK_OPS_FILE)
    table = json.loads((log.parent / trace.CHUNK_OPS_FILE).read_text())
    assert table["module"] == "jit_sample_chunk_fn" and table["scopes"] == list(trace.CHUNK_SCOPES)
    assert {"draw", "gather", "cut", "update", "update/optim", "update/polyak"} <= set(table["ops"].values())
    assert any(table["ops"].get(loop) == "update" for loop in table["loops"])
    # the run fact beside `chunk_front`: on the header, written before the
    # first launch, null; on the final record and the summary the table's count
    records = [json.loads(line) for line in open(log)]
    assert records[0]["kind"] == "header" and records[0]["chunk_body_scalars"] is None
    assert records[-1]["kind"] == "final"
    assert records[-1]["chunk_body_scalars"] == summary["chunk_body_scalars"] == table["scalars"]
    assert records[0]["chunk_body_copies"] is None
    assert records[-1]["chunk_body_copies"] == summary["chunk_body_copies"] == table["copies"]
    assert records[0]["chunk_body_gathers"] is None
    assert records[-1]["chunk_body_gathers"] == summary["chunk_body_gathers"] == table["gathers"] == 0
    assert isinstance(table["scalars"], int)
    # with --trace_dir it lies beside trace.json too
    assert json.loads((tmp_path / "tr" / trace.CHUNK_OPS_FILE).read_text()) == table
    assert (tmp_path / "tr" / "trace.json").exists()


def test_train_writes_no_table_without_a_records_file(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    summary = run_train()
    assert summary["learner_steps"] > 0
    assert summary["log_path"] == "" and summary["chunk_ops_path"] == ""
    assert not list(tmp_path.rglob(trace.CHUNK_OPS_FILE))


# --- the run fact `chunk_front` (ops/chunk_front.py) and the front's scope ---


def run_stand_in(log_path, **config):
    """train() with the device pool on the stand-in env at the source's
    shapes (obs 108, act 21: 240 floats a row, row-major by the width rule)
    and a batch of 128, on the scan leg."""
    from distributed_ddpg_tpu.envs.jax_envs import STAND_IN_ID
    from distributed_ddpg_tpu.train import train_jax

    cfg = DDPGConfig(**{**dict(
        backend="jax_tpu", env_id=STAND_IN_ID, actor_backend="device", num_actors=0, device_actor_envs=8,
        device_actor_chunk=2, actor_hidden=(32, 16, 8), critic_hidden=(32, 16, 8), batch_size=128,
        replay_capacity=4096, twin_critic=True, policy_delay=2, action_insert_layer=0, exploration="gaussian",
        learner_chunk=2, max_ingest_ratio=8.0, replay_min_size=128, warmup_uniform_steps=128,
        total_env_steps=128 + 16 * 6, eval_every=0, fused_chunk="off", seed=5, log_path=str(log_path),
    ), **config})
    return train_jax(cfg)


@pytest.mark.parametrize("native,config,front", [
    (True, {}, "cut"),  # as on a real TPU: the kernel, interpreted here
    (True, dict(prioritized=True), "xla"),  # the PER chunk overwrites the gathered rows' weights
    (False, {}, "xla"),  # off the TPU
])
def test_train_names_the_front_its_launches_took(tmp_path, as_on_a_tpu, one_chip, native, config, front):
    as_on_a_tpu(native)
    log = tmp_path / "run" / "records.jsonl"
    log.parent.mkdir()
    summary = run_stand_in(log, **config)
    assert summary["learner_steps"] > 0 and not summary["fused_chunk_active"]
    records = [json.loads(line) for line in open(log)]
    header, final = records[0], records[-1]
    assert header["kind"] == "header" and final["kind"] == "final"
    for rec in (header, final, summary):
        assert rec["chunk_front"] == front
        assert rec["chunk_collectives"] is None  # one chip reduces nothing
    assert np.isfinite(final["critic_loss"])
    table = json.loads((log.parent / trace.CHUNK_OPS_FILE).read_text())
    # the kernel's instructions read under `cut` (the CPU's compiler fuses
    # unpack_batch's slices into their readers and leaves nothing there)
    assert {"draw", "gather", "update"} <= set(table["ops"].values())
    assert ("cut" in table["ops"].values()) == (front == "cut")


# What the TPU's compiler makes of a pixel launch's `bitcast_convert_type`:
# a copy, a broadcast and a reshape of the whole block, none with an
# `op_name`, in front of the fusion that keeps the name; beside them the
# copies of the state round the launch, which have no scope on one side.
GLUE_HLO = """\
HloModule jit_sample_chunk_fn, is_scheduled=true

%body.1 (w: (f32[4], u8[4,4])) -> (f32[4], u8[4,4]) {
  %w = (f32[4]{0}, u8[4,4]{1,0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%w), index=0
  %copy.9 = f32[4]{0} copy(%gte.1)
  ROOT %tuple.1 = (f32[4]{0}, u8[4,4]{1,0}) tuple(%copy.9, %w)
}

%cond.1 (w.2: (f32[4], u8[4,4])) -> pred[] {
  %w.2 = (f32[4]{0}, u8[4,4]{1,0}) parameter(0)
  ROOT %compare.1 = pred[] compare(%w.2, %w.2), direction=LT, metadata={op_name="jit(sample_chunk_fn)/update/while/cond/lt"}
}

ENTRY %main.1 (state: f32[4], ring: f32[8,4]) -> (f32[4]) {
  %state = f32[4]{0} parameter(0)
  %ring = f32[8,4]{1,0} parameter(1)
  %fusion.1 = (f32[4,4]{1,0}, f32[4]{0}) fusion(%ring), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(sample_chunk_fn)/cut/slice"}
  %get-tuple-element.1 = f32[4,4]{1,0} get-tuple-element(%fusion.1), index=0, metadata={op_name="jit(sample_chunk_fn)/cut/slice"}
  %copy.1 = f32[4,4]{0,1} copy(%get-tuple-element.1)
  %bitcast-convert.1 = u32[4,4]{0,1} bitcast-convert(%copy.1)
  %broadcast.1 = u32[4,4,4]{1,0,2} broadcast(%bitcast-convert.1), dimensions={0,1}
  %reshape.1 = u32[4,16]{1,0} reshape(%broadcast.1)
  %and_convert_fusion.1 = u8[4,16]{1,0} fusion(%reshape.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(sample_chunk_fn)/prep/pixels/bitcast_convert_type"}
  %copy.2 = f32[4]{0} copy(%state)
  %tuple.2 = (f32[4]{0}, u8[4,4]{1,0}) tuple(%copy.2, %and_convert_fusion.1)
  %while.1 = (f32[4]{0}, u8[4,4]{1,0}) while(%tuple.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(sample_chunk_fn)/update/while"}
  %get-tuple-element.2 = f32[4]{0} get-tuple-element(%while.1), index=0
  %copy.3 = f32[4]{0} copy(%get-tuple-element.2)
  ROOT %tuple.3 = (f32[4]{0}) tuple(%copy.3)
}
"""


def test_the_compilers_nameless_steps_between_two_scopes_read_as_what_they_feed():
    assert trace.op_scopes(GLUE_HLO) == {
        "fusion.1": "cut",
        "copy.1": "prep/pixels", "bitcast-convert.1": "prep/pixels",
        "broadcast.1": "prep/pixels", "reshape.1": "prep/pixels",
        "and_convert_fusion.1": "prep/pixels",
        "while.1": "update", "copy.9": "update", "compare.1": "update",
    }  # copy.2 has a parameter before it, copy.3 the result behind it: the state's copies stay unscoped
