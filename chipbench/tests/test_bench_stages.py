"""The bound's device time split by stage: the map from each HLO
instruction to its stage and phase, the shares of a trace's op time, and
the program's scopes as the map reads them."""
import functools

import numpy as np
import pytest

import gen
import registry
import stages
from benchcase import small_cell

BILEVEL = "jit(f)/vmap(jit(solve_bilevel))"
SA1 = f"{BILEVEL}/phase1/jit(solve_sa)/search"
SA2 = f"{BILEVEL}/phase2/jit(solve_sa)/search"

# A program's text in the form XLA prints it: phase 1's SA loop runs an SGS
# loop, a fusion of objectives, an op with a bare op_name and a
# conditional; phase 2 builds the table; the entry computation adds a copy
# outside every scope.
HLO = f"""HloModule jit_f, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  ROOT %multiply.3 = f32[8]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{SA1}/while/body/objectives/mul"}}
}}

%fused_computation.2 (param_0.2: s32[8]) -> s32[8] {{
  %param_0.2 = s32[8]{{0}} parameter(0)
  ROOT %scatter.4 = s32[8]{{0}} scatter(%param_0.2, %param_0.2, %param_0.2), to_apply=%region_0.2, metadata={{op_name="scatter"}}
}}

%region_0.2 (reduce.1: s32[], reduce.2: s32[]) -> s32[] {{
  %reduce.1 = s32[] parameter(0), metadata={{op_name="reduce"}}
  %reduce.2 = s32[] parameter(1), metadata={{op_name="reduce"}}
  ROOT %add.1 = s32[] add(%reduce.1, %reduce.2), metadata={{op_name="add"}}
}}

%sgs_body (p.2: (s32[], s32[8])) -> (s32[], s32[8]) {{
  %p.2 = (s32[], s32[8]) parameter(0)
  %get-tuple-element.2 = s32[8]{{0}} get-tuple-element(%p.2), index=1
  %scatter_fusion.2 = s32[8]{{0}} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{SA1}/while/body/closed_call/vmap(jit(fitness_fn))"}}
  %get-tuple-element.3 = s32[] get-tuple-element(%p.2), index=0
  %add.9 = s32[] add(%get-tuple-element.3, %get-tuple-element.3), metadata={{op_name="{SA1}/while/body/closed_call/vmap(jit(fitness_fn))/jit(decode_full)/jit(sgs)/sgs/while/body/add"}}
  ROOT %tuple.2 = (s32[], s32[8]) tuple(%add.9, %scatter_fusion.2)
}}

%sgs_cond (p.3: (s32[], s32[8])) -> pred[] {{
  %p.3 = (s32[], s32[8]) parameter(0)
  %get-tuple-element.4 = s32[] get-tuple-element(%p.3), index=0
  ROOT %compare.1 = pred[] compare(%get-tuple-element.4, %get-tuple-element.4), direction=LT
}}

%branch_0 (b.0: f32[8]) -> f32[8] {{
  ROOT %b.0 = f32[8]{{0}} parameter(0)
}}

%branch_1 (b.1: f32[8]) -> f32[8] {{
  %b.1 = f32[8]{{0}} parameter(0)
  %negate.5 = f32[8]{{0}} negate(%b.1), metadata={{op_name="neg"}}
  ROOT %minimum_fusion.5 = f32[8]{{0}} fusion(%negate.5), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{SA1}/while/body/cond/branch_1_fun/vmap(jit(timing_sweep))/timing_sweep/min"}}
}}

%sa_body (p.1: (s32[], f32[8], s32[8])) -> (s32[], f32[8], s32[8]) {{
  %p.1 = (s32[], f32[8], s32[8]) parameter(0)
  %get-tuple-element.1 = f32[8]{{0}} get-tuple-element(%p.1), index=1
  %tuple.3 = (s32[], s32[8]) tuple(%p.1, %p.1)
  %while.2 = (s32[], s32[8]) while(%tuple.3), condition=%sgs_cond, body=%sgs_body, metadata={{op_name="{SA1}/while/body/closed_call/vmap(jit(fitness_fn))/jit(decode_full)/jit(sgs)/sgs/while"}}
  %fusion.1 = f32[8]{{0}} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{SA1}/while/body/closed_call/vmap(jit(fitness_fn))/objectives/mul" source_file="x.py" source_line=3}}
  %select_n.4 = f32[8]{{0}} select(%p.1, %fusion.1, %fusion.1), metadata={{op_name="select_n"}}
  %conditional.1 = f32[8]{{0}} conditional(%p.1, %select_n.4, %select_n.4), branch_computations={{%branch_0, %branch_1}}, metadata={{op_name="{SA1}/while/body/cond"}}
  ROOT %tuple.1 = (s32[], f32[8], s32[8]) tuple(%p.1, %conditional.1, %while.2)
}}

%sa_cond (p.4: (s32[], f32[8], s32[8])) -> pred[] {{
  %p.4 = (s32[], f32[8], s32[8]) parameter(0)
  ROOT %compare.2 = pred[] compare(%p.4, %p.4), direction=LT, metadata={{op_name="{SA1}/while/lt"}}
}}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {{
  %Arg_0.1 = f32[8]{{0}} parameter(0)
  %copy.1 = f32[8]{{0}} copy(%Arg_0.1)
  %while.1 = (s32[], f32[8], s32[8]) while(%copy.1), condition=%sa_cond, body=%sa_body, metadata={{op_name="{SA1}/while"}}
  %gather_fusion.8 = f32[8]{{0}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{BILEVEL}/phase2/sweep_table/gather"}}
  ROOT %select_fusion.7 = f32[8]{{0}} fusion(%gather_fusion.8), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{BILEVEL}/phase2/jit(_where)/select_n"}}
}}
"""

EXPECTED = {
    "while.1": ("search", "phase1"),
    "while.2": ("sgs", "phase1"),
    # Its path names only the search its caller's path has: batching cut
    # it short, so it takes the SGS loop's stage.
    "scatter_fusion.2": ("sgs", "phase1"),
    "scatter.4": ("sgs", "phase1"),      # inside the fusion, bare op_name
    "add.1": ("sgs", "phase1"),          # the scatter's reducer
    "add.9": ("sgs", "phase1"),
    "fusion.1": ("objectives", "phase1"),
    "select_n.4": ("search", "phase1"),  # bare op_name in the SA body
    "conditional.1": ("search", "phase1"),
    "negate.5": ("search", "phase1"),    # bare, in a conditional branch
    "minimum_fusion.5": ("timing_sweep", "phase1"),
    "compare.1": ("sgs", "phase1"),      # a loop's condition, no metadata
    "copy.1": (None, None),
    "gather_fusion.8": ("sweep_table", "phase2"),
    "select_fusion.7": (None, "phase2"),
}


def test_named_takes_the_innermost_and_unwraps():
    path = f"{SA2}/while/body/vmap(jit(sgs))/while/body/closed_call/gather"
    assert stages.named(path, stages.STAGES) == "sgs"
    assert stages.named(path, stages.PHASES) == "phase2"
    assert stages.named("jit(f)/jit(sgs_helper)/add", stages.STAGES) is None
    assert stages.named("select_n", stages.STAGES) is None
    assert stages.named("", stages.PHASES) is None


def test_attribute_follows_paths_callers_fusions_and_branches():
    table = stages.attribute(HLO)
    for name, want in EXPECTED.items():
        assert table[name] == want, name
    assert stages.complete(table)
    assert not stages.complete({n: (s, None) for n, (s, _) in table.items()})


def test_split_partitions_the_window():
    ops = {"while.1": 1.0, "while.2": 2.0, "scatter_fusion.2": 30.0,
           "add.9": 5.0, "fusion.1": 20.0, "select_n.4": 4.0,
           "minimum_fusion.5": 25.0, "gather_fusion.8": 6.0,
           "select_fusion.7": 2.0, "copy.1": 4.5, "not-in-program.3": 0.4}
    got = stages.split({n: {"seconds": s, "count": 1}
                        for n, s in ops.items()}, stages.attribute(HLO))
    total = sum(ops.values())
    assert got["sgs"] == pytest.approx(100 * 37 / total)
    assert got["timing_sweep"] == pytest.approx(100 * 25 / total)
    assert got["objectives"] == pytest.approx(100 * 20 / total)
    assert got["search"] == pytest.approx(100 * 5 / total)
    assert got["sweep_table"] == pytest.approx(100 * 6 / total)
    # The copy, the code between phases, and the op the program lacks
    # (0.4% of the time, under the limit).
    assert got["unattributed"] == pytest.approx(100 * 6.9 / total)
    assert got["phase1"] == pytest.approx(100 * 87 / total)
    assert got["phase2"] == pytest.approx(100 * 8 / total)
    assert sum(got[k] for k in stages.STAGES + (stages.UNATTRIBUTED,)) == \
        pytest.approx(100)


def test_missing_time_gives_no_split():
    table = stages.attribute(HLO)
    ops = {"fusion.1": {"seconds": 99.0, "count": 1},
           "fusion.404": {"seconds": 0.6, "count": 1}}
    assert stages.split(ops, table) is None
    ops["fusion.404"]["seconds"] = 0.4
    assert stages.split(ops, table)["objectives"] == pytest.approx(
        100 * 99 / 99.4)
    assert stages.split({}, table) is None


STAGE_READERS = {
    "bound.sgs_share": "sgs", "bound.timing_sweep_share": "timing_sweep",
    "bound.sweep_table_share": "sweep_table",
    "bound.objectives_share": "objectives", "bound.search_share": "search",
    "bound.phase1_share": "phase1",
    "bound.unattributed_share": stages.UNATTRIBUTED}


def _ctx(trace_ops):
    return {"cell": small_cell("bound.paper_s1"), "spans": {},
            "trace": {"ops": trace_ops, "window_s": 1.0, "busy_s": 1.0},
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("metric", sorted(STAGE_READERS))
def test_readers_share_one_rebuilt_program(metric, monkeypatch):
    built = []
    monkeypatch.setattr(stages, "_MAPS", {})
    monkeypatch.setattr(stages, "program_text",
                        lambda cell: built.append(cell.name) or HLO)
    ops = {"while.2": {"seconds": 3.0, "count": 1},
           "fusion.1": {"seconds": 1.0, "count": 1},
           "copy.1": {"seconds": 1.0, "count": 1}}
    want = stages.split(ops, stages.attribute(HLO))[STAGE_READERS[metric]]
    for name in STAGE_READERS:
        registry.reader(name)(_ctx(ops))
    assert registry.reader(metric)(_ctx(ops)) == pytest.approx(want)
    assert built == ["bound.paper_s1"]


def test_no_number_without_trace_chip_or_scopes(monkeypatch):
    read = registry.reader("bound.sgs_share")
    monkeypatch.setattr(stages, "_MAPS", {})
    monkeypatch.setattr(stages, "program_text", lambda cell: HLO.replace(
        "phase2", "later"))
    ops = {"while.2": {"seconds": 3.0, "count": 1}}
    assert read(dict(_ctx(ops), trace=None)) is None
    assert read(dict(_ctx(ops), device_kind=None)) is None
    # A program without every scope (one from before them) gives nothing.
    assert read(_ctx(ops)) is None


def _bound_program(n=2, **kw):
    """The bound's batch program at a test size: a few instances, pop 8,
    4 iterations, as the cell's driver builds it."""
    import jax
    import jax.numpy as jnp
    from repro.core.instance import Instance, Job, pack, stack_packed
    from repro.core.solvers import solve_bilevel_batch
    from repro.core.solvers.annealing import SAConfig

    powers, speeds = gen.fleet("homog", 3)
    year = gen.synthesize("AU-SA", 5, 1)
    rng = gen.seed_rng(1, 2, 0)
    packed, cums = [], []
    for _ in range(n):
        jobs = [gen.paper_job(rng, 2, 7.0, 24) for _ in range(3)]
        packed.append(pack(Instance(jobs=tuple(Job(a, b, e)
                                               for a, b, e in jobs),
                                    powers_kw=powers, speeds=speeds),
                           pad_tasks=6))
        cums.append(gen.cumulative_f32(gen.window(year, 10, 200)))
    args = (stack_packed(packed), jnp.asarray(np.stack(cums)),
            jax.random.split(jax.random.key(0), n))
    sa = SAConfig(pop=8, iters=4, sweeps=2)
    fn = jax.jit(functools.partial(solve_bilevel_batch, objective="carbon",
                                   stretch=1.0, cfg1=sa, cfg2=sa, **kw))
    return fn, args


def test_program_names_every_stage_and_phase():
    """The bound compiled on the CPU names every stage but the table,
    which only a TPU builds, and each phase; few instructions fall under
    no stage."""
    fn, args = _bound_program()
    table = stages.attribute(fn.lower(*args).compile().as_text())
    named = {s for s, _ in table.values()}
    assert set(stages.STAGES) - {"sweep_table"} <= named
    assert {p for _, p in table.values()} >= set(stages.PHASES)
    loose = sum(s is None for s, _ in table.values())
    assert loose < 0.05 * len(table)


def test_program_names_the_table_on_a_tpu():
    from unittest import mock

    # The kernels stay off: on the CPU they lower only in interpret mode.
    fn, args = _bound_program(use_kernels=False)
    with mock.patch("jax.default_backend", return_value="tpu"):
        text = fn.lower(*args).as_text(debug_info=True)
    assert "/sweep_table/" in text


def test_names_match_the_program():
    from repro.obs import scopes
    assert scopes.STAGES == stages.STAGES
    assert scopes.PHASES == stages.PHASES
