"""The solver's whole-run program, compiled by the TPU's compiler for a
described (not attached) v5e: its loop copies no field.

``_run_steps`` advances two chunk-kernel calls per loop iteration so that
XLA's while loop needs no copy of the carry (six fields an iteration
otherwise: 28.6 % of device time at 3600 x 28800, PERF.md), and ``_wide_run``
two refresh-and-call rounds on the carried widened frame (six frames an
iteration otherwise: 26.4 % of the walled domain's).  On the CPU the
copies cost nothing and show nowhere, so a later jax, or a later edit of the
loop, could bring them back unseen; the compiled program shows them.

Nothing runs here and no time is read.  The width is narrow (a compile takes
seconds; at the benchmark's 3600 it takes two minutes) but the six fields
(201 MB) are still far beyond what the chip holds on chip.
"""

import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))

NX, NY = 1022, 8190
FIELD = rf"f32\[(1,)?{NY + 2},{NX + 2}\]"
SIX_FIELDS = 6 * 4 * (NY + 2) * (NX + 2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_leg(topo):
    """The Euler step and ``steps`` more in one region, the program's own
    ``fused_runner``, pinned for one described chip — or for the 2 x 2 with
    ``mesh=(2, 2)``, every chip holding the same local field; with
    ``multistep=True`` the ``steps``-step program of ``make_stepper``, with
    ``multistep="carried"`` its carried form, frames in and frames out."""
    from jax.sharding import NamedSharding, PartitionSpec

    import mpi4jax_tpu as mpx
    import shallow_water as sw

    def compile_leg(mode, steps, periodic_x=True, mesh=(1, 1),
                    multistep=False):
        py, px = mesh
        cfg = sw.Config(nx=NX * px, ny=NY * py, nproc_y=py, nproc_x=px,
                        periodic_x=periodic_x)
        _mesh, comm = sw.make_mesh_and_comm(cfg,
                                            devices=topo.devices[:py * px])
        field = jax.ShapeDtypeStruct(
            (py * px, NY + 2, NX + 2), jnp.float32,
            sharding=NamedSharding(comm.mesh,
                                   PartitionSpec(comm.mesh.axis_names)))
        arg = sw.State(*[field] * 6)
        if multistep:
            _first_step, program = sw.make_stepper(cfg, comm, fast=mode)
            if multistep == "carried":
                program = program.carried
                arg = (jax.ShapeDtypeStruct(
                    (py * px, FRAME_NY, FRAME_NX), jnp.float32,
                    sharding=field.sharding),) * 6
        else:
            program, _ = sw.fused_runner(cfg, comm, mode)
        cache = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            return mpx.compile(program, arg, steps)._call
        finally:
            jax.config.update("jax_enable_compilation_cache", cache)

    return compile_leg


def _computation(text, name):
    """The instructions of the computation ``name`` of an HLO module."""
    m = re.search(rf"^{re.escape(name)} \(.*?\{{\n(.*?)^\}}", text,
                  re.M | re.S)
    assert m, f"no computation {name}"
    return m.group(1).splitlines()


def _loop_bodies(text):
    return [_computation(text, body) for body
            in re.findall(r" while\(.*?body=(%[\w.\-]+)", text)]


def _split_at_loops(text):
    """``(loop bodies, every line outside them)`` of an HLO module."""
    bodies = _loop_bodies(text)
    body_lines = {ln for body in bodies for ln in body}
    return bodies, [ln for ln in text.splitlines() if ln not in body_lines]


def _kernel_calls(lines, steps_a_call=2, kind="sw_steps", euler=False):
    name = f"{kind}_x{steps_a_call}" + ("_euler" if euler else "")
    return [ln for ln in lines if re.match(
        rf"\s*%{name}[.\d]* = .* custom-call\(", ln)]


def _trip_counts(text):
    """The trip count of each ``while`` of an HLO module: the constant its
    condition compares the counter with."""
    counts = []
    for cond in re.findall(r" while\(.*?condition=(%[\w.\-]+)", text):
        (n,) = re.findall(r" constant\((\d+)\)",
                          "\n".join(_computation(text, cond)))
        counts.append(int(n))
    return counts


def _field_copies(lines):
    return [ln.split(" copy(")[0].strip() for ln in lines
            if re.match(rf"\s*(ROOT )?%[\w.\-]+ = {FIELD}\S* copy\(", ln)]


@pytest.mark.parametrize("mode,kind,sets", [
    # the whole-step kernel on the ``State``, by name while the mode lives:
    # one spare set of six fields and no more, the second call of an
    # iteration writes where the first has read
    ("pallas2", "sw_steps", 1.1),
    # what ``auto`` picks on one periodic chip since PR 38: the wide-halo
    # pair on the carried frame (the walled leg's 2.26 sets, test below)
    ("auto", "sw_wide", 2.4),
])
def test_the_loop_of_the_compiled_leg_copies_no_field(compile_leg, mode,
                                                      kind, sets):
    """One periodic chip at the benchmark's 70 steps: 35 two-step chunks,
    17 pairs in the loop and one chunk after it."""
    leg = compile_leg(mode, 70)
    text = leg.as_text()
    (body,) = _loop_bodies(text)
    assert len(_kernel_calls(body, 2, kind)) == 2, [
        ln.split(" = ")[0].strip() for ln in body]
    assert _trip_counts(text) == [17]
    assert not _field_copies(body) and not _frame_copies(body)
    temp = leg.memory_analysis().temp_size_in_bytes
    assert temp < sets * SIX_FIELDS, (temp, SIX_FIELDS)


@pytest.mark.parametrize("mode,steps,loops,in_line", [
    ("pallas2", 6, 0, 3),    # three chunks: a loop of one trip, inlined
    ("pallas2", 11, 1, 1),   # five chunks (two pairs, one after) + 1 step
])
def test_short_runs_hold_two_spare_sets_at_most(compile_leg, mode, steps,
                                                loops, in_line):
    """The cost of the loop having one form, held where it was measured.
    Up to three chunks the loop is one trip, which XLA inlines; kernel
    calls in line are given one spare set of fields or two by their count,
    where the parent's loop held one (402,782,208 B against 202,520,576 B
    at six steps here).  The same happens to an odd chunk count of five
    or more with a step after it: the odd chunk and the one-step call in
    line behind the loop.  Over every count from 1 to 23 steps it was never
    more than two sets."""
    leg = compile_leg(mode, steps)
    text = leg.as_text()
    bodies, entry = _split_at_loops(text)
    assert len(bodies) == loops
    for body in bodies:
        assert len(_kernel_calls(body, int(mode[-1]))) == 2
    assert len(_kernel_calls(entry, int(mode[-1]))) == in_line
    # the steps the chunks leave over, each a one-step call in line
    assert len(_kernel_calls(entry, 1)) == steps % int(mode[-1])
    assert not _field_copies(text.splitlines())
    temp = leg.memory_analysis().temp_size_in_bytes
    assert temp < 2.1 * SIX_FIELDS, (temp, SIX_FIELDS)


def test_the_periodic_ten_step_call_copies_fields_at_its_boundary_only(
        compile_leg):
    """The call the documented host loop made 44 times a run on one
    periodic chip until PR 38 gave that chip the carried frame, and makes
    under ``fast="pallas2"`` while the mode lives (``make_stepper``'s
    ``multistep`` at ``num_multisteps`` = 10; what the benchmark's
    ``sw3600x28800_solve.1chip`` calls now is the carried call held
    below): five two-step chunks — a loop of two
    trips with two kernel calls in its body, the fifth call behind it — no
    field copied in the loop, twelve at the most at the region's entry and
    exit (changes of layout of the six parameters and the six results: 6
    at this size, 12 at 3600 x 28800, where they are 15 % of the call),
    and two spare sets of fields at the most beside arguments and results
    (1.00 here, 2.05 at 3600 x 28800: 5,133,318,144 B, which is what lets
    four states and a call's temporaries fit a 16.9e9-byte chip; both
    sizes compiled for a described v5e, PR 35)."""
    call = compile_leg("pallas2", 10, multistep=True)
    text = call.as_text()
    (body,), entry = _split_at_loops(text)
    assert _trip_counts(text) == [2]
    assert len(_kernel_calls(body)) == 2 and len(_kernel_calls(entry)) == 1
    assert not _kernel_calls(entry, 1) and not _kernel_calls(entry, 1,
                                                             euler=True)
    assert not _field_copies(body)
    assert len(_field_copies(entry)) <= 12, _field_copies(entry)
    mem = call.memory_analysis()
    assert mem.temp_size_in_bytes < 2.1 * SIX_FIELDS, (
        mem.temp_size_in_bytes, SIX_FIELDS)
    assert mem.argument_size_in_bytes < 1.01 * SIX_FIELDS


# the frame: 15 cells a side, then rows up to a multiple of 8 and columns
# to one of 128 (``_wide_exchange``'s dead cells): 8222 x 1054 -> 8224 x 1152
FRAME_NY, FRAME_NX = NY + 32 + -(NY + 32) % 8, NX + 32 + -(NX + 32) % 128
FRAME = rf"f32\[(1,)?{FRAME_NY},{FRAME_NX}\]"
SIX_FRAMES = 6 * 4 * FRAME_NY * FRAME_NX


def _frame_copies(lines, layout=""):
    """Whole-frame copies; ``layout="{1,0"`` keeps the row-major ones (the
    carry's), ``"{0,1"`` the transposing ones."""
    return [ln.split(" copy(")[0].strip() for ln in lines
            if re.match(rf"\s*(ROOT )?%[\w.\-]+ = {FRAME}{re.escape(layout)}"
                        r"\S* copy\(", ln)]


def test_the_walled_leg_builds_one_frame_and_crops_once(compile_leg):
    """A closed basin on one chip, what ``auto`` gives every deployment but
    the single periodic chip: the wide-halo pair kernel on the carried
    widened frame.  70 steps after the Euler one: the frame is built before
    the loop and cropped after it; the loop's body holds two kernel calls,
    no whole-frame copy and neither build nor crop; 35 chunk calls are 17
    trips and one call behind the loop; and the whole is what ``leg_plan``
    says."""
    import shallow_water as sw

    leg = compile_leg("auto", 70, periodic_x=False)
    text = leg.as_text()
    (body,), outside = _split_at_loops(text)
    plan = sw.leg_plan(sw.Config(nx=NX, ny=NY, periodic_x=False), "auto", 71)
    assert plan == {"steps": 71, "steps_per_kernel_call": 2,
                    "euler_calls": 1, "chunk_calls": 35,
                    "single_step_calls": 0, "frames_built": 1,
                    "band_refreshes": 35, "crops": 1}

    assert len(_kernel_calls(body, 2, "sw_wide")) == 2
    assert not _frame_copies(body)
    assert _trip_counts(text) == [plan["chunk_calls"] // 2]  # a pair a trip
    assert (len(_kernel_calls(outside, 2, "sw_wide"))
            == plan["chunk_calls"] % 2)  # the odd one, behind the loop
    assert len(_kernel_calls(outside, 1, "sw_wide", euler=True)) == 1
    assert not _kernel_calls(text.splitlines())  # no whole-step kernel here

    assert len(_frames_made(outside)) == 6 * plan["frames_built"]
    assert len(_crops(outside)) == 6 * plan["crops"]
    assert not _frames_made(body) and not _crops(body)
    # the carry's frames, one spare set of them and the bands (2.26 read,
    # 2.27 with the copies): the second call writes where the first read
    temp = leg.memory_analysis().temp_size_in_bytes
    assert temp < 2.4 * SIX_FIELDS, (temp, SIX_FIELDS)


def test_the_walled_leg_on_a_2x2_copies_no_carried_frame(compile_leg):
    """The same leg on the described 2 x 2 (every chip the one-chip case's
    local field): the body holds two rounds — two kernel calls, two band
    refreshes of four permutes each, every exchange on the caller's token
    and ordered by data alone — and no row-major whole-frame copy, which
    is what the carry's copies were.  (The transposing ones that cut the x
    bands out of a frame are another matter: PERF.md section 7.)"""
    leg = compile_leg("auto", 70, periodic_x=False, mesh=(2, 2))
    text = leg.as_text()
    (body,), outside = _split_at_loops(text)
    assert len(_kernel_calls(body, 2, "sw_wide")) == 2
    assert not _frame_copies(body, "{1,0")
    assert sum(" collective-permute-start(" in ln for ln in body) == 2 * 4
    assert _trip_counts(text) == [17]
    assert len(_kernel_calls(outside, 2, "sw_wide")) == 1
    temp = leg.memory_analysis().temp_size_in_bytes
    assert temp < 2.4 * SIX_FIELDS, (temp, SIX_FIELDS)


@pytest.mark.parametrize("multistep,steps,periodic_x,loops,in_line", [
    (False, 6, False, 0, 3),   # Euler + 3 rounds: one odd trip, inlined
    (True, 6, True, 0, 3),     # a call off the frame + 2 rounds, inlined
    (False, 10, False, 1, 1),  # Euler + 5 rounds: two pairs, one behind
    (True, 10, True, 1, 1),    # solve()'s default: 1 + 4, two pairs
])
def test_short_wide_runs_hold_no_more_than_the_loop(compile_leg, multistep,
                                                    steps, periodic_x, loops,
                                                    in_line):
    """``wide2`` below the benchmark's length, a leg (walled) and a
    ``multistep`` (periodic, one rank) each with an even and an odd count
    of rounds.  Up to three rounds the loop is one trip, which XLA
    inlines; from four on it is a loop of pairs.  Either way no whole
    frame is copied, and — unlike ``pallas2``'s calls in line — the
    temporaries stay where the loop's are: over every count from 1 to 23
    steps, walled and periodic, leg and ``multistep``, 2.262–2.268 sets of
    fields (the parent's 2.262–2.272 with its copies)."""
    leg = compile_leg("wide2", steps, periodic_x=periodic_x,
                      multistep=multistep)
    text = leg.as_text()
    bodies, outside = _split_at_loops(text)
    assert len(bodies) == loops
    for body in bodies:
        assert len(_kernel_calls(body, 2, "sw_wide")) == 2
    assert len(_kernel_calls(outside, 2, "sw_wide")) == in_line
    assert not _frame_copies(text.splitlines())
    temp = leg.memory_analysis().temp_size_in_bytes
    assert temp < 2.4 * SIX_FIELDS, (temp, SIX_FIELDS)


def _entry_layouts(text):
    """The parameters' and the results' shapes with their layouts, in
    order, from the module's ``entry_computation_layout``."""
    line = text.splitlines()[0]
    start = end = line.index("entry_computation_layout={") + 26
    depth = 1
    while depth:  # to the brace that closes the attribute
        depth += {"{": 1, "}": -1}.get(line[end], 0)
        end += 1
    return re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", line[start:end])


def _frames_made(lines):  # a field widened to the frame
    return [ln for ln in lines
            if re.match(rf"\s*(ROOT )?%[\w.\-]+ = {FRAME}\S* concatenate\(",
                        ln)]


def _crops(lines):  # a frame cut back to a field
    return [ln for ln in lines
            if re.match(rf"\s*(ROOT )?%[\w.\-]+ = {FIELD}\S* slice\(", ln)]


@pytest.mark.parametrize("periodic_x", [False, True])
@pytest.mark.parametrize("steps,trips,in_line", [
    (6, [], 3),     # a call off the parameters and two rounds, inlined
    (10, [2], 1),   # solve()'s default: the call and four rounds, two pairs
    (12, [2], 2),   # ... and an odd round behind the loop
])
def test_the_carried_multistep_neither_builds_nor_crops_a_frame(
        compile_leg, steps, trips, in_line, periodic_x):
    """What ``run_multisteps`` calls 44 times a published run — on the
    closed basin, and since PR 38 on the periodic chip too, where the x
    bands of a refresh are slices of the frame itself and not zeros (the
    case ``_wide_refresh`` writes its bands with ``dynamic_update_slice``
    for: a scatter there kept a whole-frame copy a field a refresh) — at
    a width whose unaligned frame XLA:TPU would hand over transposed, as
    it did at
    3600 x 28800 (``f32[1,8222,1054]{1,2,0}``; aligned it is
    ``f32[1,8224,1152]``): ``steps`` steps on the six frames the call
    before left, six frames out.  A ``sw_wide_x2`` call straight off the
    parameters, rounds of a band refresh and a call (two an iteration of
    the loop), a last refresh — with no frame built, none cropped and no
    frame copied, in the loop or at the boundary: the aligned frame's
    default layout there is the row-major one the kernel reads and writes
    (**0** whole-frame copies where the unaligned frame paid twelve, six
    parameters in and six results out: PERF.md section 6, PR 36), and the
    call holds one set of six frames beside its parameters and results
    where the copies' buffers made it two."""
    import shallow_water as sw

    carried = compile_leg("auto", steps, periodic_x=periodic_x,
                          multistep="carried")
    text = carried.as_text()
    bodies, outside = _split_at_loops(text)
    plan = sw.run_plan(sw.Config(nx=NX, ny=NY, periodic_x=periodic_x),
                       "auto", 44, steps)["multistep"]
    rounds = steps // 2
    assert (plan["chunk_calls"], plan["band_refreshes"],
            plan["frames_built"], plan["crops"]) == (rounds, rounds, 0, 0)
    assert _trip_counts(text) == trips
    for body in bodies:
        assert len(_kernel_calls(body, 2, "sw_wide")) == 2
        assert not _frame_copies(body)
        # four dynamic-update-slices a frame a refresh: the bands alone
        assert sum(" dynamic-update-slice(" in ln for ln in body) == 2 * 24
    assert len(_kernel_calls(outside, 2, "sw_wide")) == in_line
    assert not _kernel_calls(outside, 1, "sw_wide", euler=True)
    lines = text.splitlines()
    assert not _frames_made(lines) and not _crops(lines)
    assert not _frame_copies(lines)
    boundary = _entry_layouts(text)
    assert boundary == [
        f"f32[1,{FRAME_NY},{FRAME_NX}]{{2,1,0:T(8,128)}}"] * 12, boundary
    assert (sum(" dynamic-update-slice(" in ln for ln in lines)
            == 24 * (plan["band_refreshes"] - sum(trips) * 2 + 2 * len(trips)))
    # one set of six frames: the first kernel call reads the parameters
    # where they lie and the last refresh writes the results' own buffers
    # (1.00 sets here; 2,568,811,520 B at 3600 x 28800, where the
    # unaligned frame's call held 5,137,299,456)
    temp = carried.memory_analysis().temp_size_in_bytes
    assert temp < 1.2 * SIX_FRAMES, (temp, SIX_FRAMES)


def test_stepping_by_hand_still_builds_and_crops_a_frame_a_call(
        compile_leg):
    """``multistep(state, 10)``, the ``State -> State`` program beside the
    carried one, as it was: the frame built at the top (six
    ``concatenate``s to the frame's shape), a call off the fresh frame and
    four rounds (two pairs in the loop), six crops at the bottom."""
    by_hand = compile_leg("auto", 10, periodic_x=False, multistep=True)
    text = by_hand.as_text()
    (body,), outside = _split_at_loops(text)
    assert len(_kernel_calls(body, 2, "sw_wide")) == 2
    assert _trip_counts(text) == [2]
    assert len(_kernel_calls(outside, 2, "sw_wide")) == 1
    assert len(_frames_made(outside)) == 6 and len(_crops(outside)) == 6
    assert not _frames_made(body) and not _crops(body)
    assert not _frame_copies(body)
    temp = by_hand.memory_analysis().temp_size_in_bytes
    assert temp < 2.4 * SIX_FIELDS, (temp, SIX_FIELDS)
