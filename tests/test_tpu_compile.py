"""The coarse scan compiled for a DESCRIBED TPU v5e at the benchmark's
real shapes (no chip: nothing runs, so no time is taken). What no
XLA:CPU test can see: where the compiler puts the stored scores and
what a compiled step holds. All such compiles live in this one file and
describe the topology inside a fixture (only the worker that is given
this file loads the TPU's library)."""

import re

import jax
import jax.numpy as jnp
import pytest

import scan_alone
from predictionio_tpu.ops import retrieval

TILE, KP = 1 << 18, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache

    try:
        chip = scan_alone.described_chip()
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield chip
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding):
    """(shape, dtype) -> an argument described, not made, there."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


def _compiled(chip, b, nt, d, mode="bf16", sides=None, program=None):
    """``_coarse_topk`` (or ``program`` in its place) for ``b`` queries
    over ``nt`` tiles, the side arrays as a catalog stores them
    (``sides``: another shape)."""
    arg = _on(chip)
    sides = sides or retrieval.side_shape(nt, TILE)
    key = b, nt, d, mode, sides, program is not None
    if key not in _COMPILED:  # the int8 programs take 13 s each
        _COMPILED[key] = (program or retrieval._coarse_topk).lower(
            arg((b, d), jnp.float32),
            arg((nt, TILE, d), jnp.bfloat16 if mode == "bf16" else jnp.int8),
            None if mode == "bf16" else arg(sides, jnp.float32),
            arg(sides, jnp.int32), k=KP, mode=mode,
        ).compile()
    return _COMPILED[key]


PAST = 1 << 30  # rows of a catalog whose stored scores are past retrieval._UNCUT
_COMPILED = {}


def _loop_texts(text):
    """The ``while`` bodies in a compiled module's text: the scan's, one
    a chunk."""
    bodies = []
    for name in re.findall(r"while\(.*?body=%?([\w.\-]+)", text):
        start = text.index(f"\n%{name} ")
        bodies.append(text[start: text.index("\n}\n", start)])
    return bodies


def _loop_text(text):
    """The (first) scan's ``while`` body in a compiled module's text."""
    return _loop_texts(text)[0]


def _loop_body(text):
    """The instructions of that body: [(result shape as text, opcode)]
    (a fusion of several results is named by its first)."""
    return [
        (kind.lstrip("(").split("{")[0], op) for kind, op in re.findall(
            r" = (\(.*?\)|\S+) (\w[\w\-]*)\(", _loop_text(text)
        )
    ]


@pytest.mark.parametrize("b,nt,d", [
    (8, 36, 64), (16, 36, 64),     # yambda: the saturated cell's two batches
    (16, 46, 64),                  # a chip of the sharded catalog
    (16, 16, 128),                 # both Taobao configurations' tiles
])
def test_a_batchs_step_selects_nothing_and_stores_its_scores(one_chip, b, nt, d):
    """The compiled loop body of a served batch holds no ``sort`` (no
    selection, no merge), and the program's temporaries are the stored
    scores (B x NT x T x 4 bytes) and a little: what the bound of
    ``scan_chunk`` reckons with."""
    assert retrieval.scan_chunk(b, d, "bf16", PAST) == b
    compiled = _compiled(one_chip, b, nt, d)
    ops = _loop_body(compiled.as_text())
    assert ops and not [o for o in ops if o[1] == "sort"]
    stored = b * nt * TILE * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert stored <= temp <= 1.15 * stored


def test_a_singles_temporaries_stay_where_they_were(one_chip):
    """B = 1 over yambda's 36 tiles: the 38 MB of stored scores sit in
    the compiler's own memory space; under 1 MB of temporaries."""
    compiled = _compiled(one_chip, 1, 36, 64)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert not [o for o in _loop_body(compiled.as_text()) if o[1] == "sort"]


@pytest.mark.parametrize("b", [32, 64])
def test_a_batch_beyond_the_bound_is_chunks_within_it(one_chip, b):
    """32 and 64 queries at rank 64 (1.2 / 2.4 GB of scores against
    1.2 GB of tiles): two and four loops of 16 queries, one after
    another in the one program — no ``sort`` in any of them, no copy of
    the tiles, and the temporaries of ONE chunk's stored scores: B = 16's
    and under 64 MB more."""
    assert retrieval.scan_chunk(b, 64, "bf16", PAST) == 16
    compiled = _compiled(one_chip, b, 36, 64)
    text = compiled.as_text()
    loops = _loop_texts(text)
    assert len(loops) == b // 16
    for body in loops:
        assert " sort(" not in body and "f32[36,16,2048,128]" in body
    assert f"f32[36,{b}," not in text  # no more than a chunk's scores stacked
    assert not re.search(r"bf16\[36,262144,64\]\S* copy\(", text)
    sixteen = _compiled(one_chip, 16, 36, 64).memory_analysis().temp_size_in_bytes
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert sixteen <= temp <= sixteen + (64 << 20)


@pytest.mark.parametrize("nt,d,mode,loops", [
    (4, 10, "bf16", 1),    # the templates' default rank over 1 M rows
    (4, 32, "int8", 1),    # bench.py's retrieval rung
    (36, 10, "bf16", 4),   # the default rank over yambda's rows
    (36, 32, "int8", 4),
])
def test_below_rank_64_a_pass_takes_what_stores_604_mb(
        one_chip, monkeypatch, nt, d, mode, loops):
    """64 queries under rank 64, where the first bound alone would make
    32, 16 or 64 loops of them: ONE loop over 1 M rows, four of 16 over
    9.4 M, no ``sort`` in any, and — the rank-10 tiles are re-laid, 16
    values a row — one copy of the tiles a call, not one a chunk: the
    temporaries of 16 queries and under 64 MB more."""
    monkeypatch.setattr(retrieval, "_UNCUT", 16 * 36 * TILE * 4)
    assert retrieval.scan_chunk(64, d, mode, nt * TILE) == 64 // loops
    # these shapes are no other test's: nothing traced without the bytes
    text = _compiled(one_chip, 64, nt, d, mode).as_text()
    bodies = _loop_texts(text)
    assert len(bodies) == loops
    assert not [body for body in bodies if " sort(" in body]
    assert f"f32[{nt},64," not in text or loops == 1
    if loops > 1:
        sixteen = _compiled(one_chip, 16, nt, d, mode)
        assert len(_loop_texts(sixteen.as_text())) == 1
        temp = _compiled(one_chip, 64, nt, d, mode).memory_analysis()
        assert temp.temp_size_in_bytes <= (
            sixteen.memory_analysis().temp_size_in_bytes + (64 << 20))


@pytest.mark.parametrize("b", [1, 8])
def test_the_packed_masked_scan_keeps_its_loop(one_chip, b):
    """Both Taobao cells' masked scan (16 tiles, rank 128) from ONE
    packed buffer against the same program from separate arrays: the
    unpacking is a preamble — the compiled loop body holds the ops it
    held, none more — and the temporaries stay within 1 MB."""
    from collections import Counter

    from predictionio_tpu.ops.topk import Rules

    nt, d, e = 16, 128, 128  # a storefront query's seen list: 65-128 rows
    arg = _on(one_chip)
    resident = Rules(
        arg((nt * TILE,), jnp.uint8), (arg((nt * TILE,), jnp.int32),),
        None, None, None,
    )
    separate = resident._replace(
        qcat=arg((b, 1), jnp.int32), has_cat=arg((b,), jnp.bool_),
        ex=arg((b, e), jnp.int32),
    )
    layout = retrieval.Layout(d, 1, e)
    catalog = (arg((nt, TILE, d), jnp.bfloat16), None,
               arg(retrieval.side_shape(nt, TILE), jnp.int32))
    was = retrieval._coarse_topk_masked.lower(
        arg((b, d), jnp.float32), *catalog, separate, k=KP, mode="bf16",
    ).compile()
    packed = retrieval._coarse_topk_masked.lower(
        arg((b, sum(hi - lo for lo, hi in layout.bounds())), jnp.int32),
        *catalog, resident, k=KP, mode="bf16", layout=layout,
    ).compile()
    held = Counter(op for _, op in _loop_body(was.as_text()))
    holds = Counter(op for _, op in _loop_body(packed.as_text()))
    assert holds and not holds - held, holds - held
    assert abs(
        packed.memory_analysis().temp_size_in_bytes
        - was.memory_analysis().temp_size_in_bytes
    ) <= 1 << 20


# -- the per-row side arrays: a step's slice is one dense block (PR 42) ---------

# what a step costs beyond bookkeeping: its passes over memory
_PASSES = ("fusion", "reduce", "copy", "convolution", "sort", "dynamic-slice",
           "dynamic-update-slice", "select", "transpose")


def _passes(text):
    return [(shape, op) for shape, op in _loop_body(text) if op in _PASSES]


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("nt,mode", [
    (36, "bf16"),    # yambda
    (46, "bf16"),    # a chip of the sharded catalog
    (184, "int8"),   # the whole marketplace stored int8
])
def test_a_step_reads_its_side_arrays_as_one_dense_block(one_chip, nt, mode, b):
    """The scans of the cells' shapes: the program's side arrays are
    ``[NT,2048,128]`` in whole memory tiles of 8 x 128, a step slices
    ``[1,2048,128]`` out of them, nothing in the loop is
    ``[NT,262144]`` (where a step's row is one sublane of every tile),
    and the body holds no ``copy`` of a tile or of a side array."""
    text = _compiled(one_chip, b, nt, 64, mode).as_text()
    entry = text[text.index("ENTRY "):]
    sides = 1 if mode == "bf16" else 2
    assert len(re.findall(
        rf"[sf]32\[{nt},2048,128\]{{2,1,0:T\(8,128\)}} parameter\(", entry
    )) == sides
    assert f"[{nt},262144]" not in text
    whole = text[text.index("\n%"):]  # every computation: the fused ones too
    slices = re.findall(
        r"= ([sf]32)\[1,2048,128\]{2,1,0:T\(8,128\)} dynamic-slice\(", whole
    )
    assert {"s32"} <= set(slices) and ("f32" in slices) == (mode == "int8")
    ops = _passes(text)
    assert ops and not [o for o in ops if o[1] in ("copy", "sort")], ops


def _readers(text, nt):
    """How many fusions of the loop body take each ``[NT,2048,128]`` side
    array as an operand: {"f32": the row scales', "s32": the row ids'}."""
    body = _loop_text(text)
    sides = dict(re.findall(
        rf"%([\w.\-]+) = ([sf]32)\[{nt},2048,128\]\S* get-tuple-element\(", body
    ))
    assert set(sides.values()) <= {"f32", "s32"} and sides
    fusions = re.findall(r" fusion\(([^)]*)\), kind=", body)
    return {
        dtype: sum(f"%{name}" in ops.split(", ") for ops in fusions)
        for name, dtype in sides.items()
    }


def _without_the_barrier(monkeypatch):
    """``_coarse_topk`` with the step of PR 42 — the scores and their
    maxima two consumers of the scaled and guarded row: the served code
    less one call, traced anew (a jit of its own: no cached trace)."""
    monkeypatch.setattr(retrieval, "_kept_once", lambda kept: kept)
    scan = retrieval._coarse_topk.__wrapped__.__wrapped__
    return jax.jit(lambda *a, **kw: scan(*a, **kw), static_argnames=("k", "mode"))


SERVED_SINGLES = [
    (36, "bf16"),    # yambda
    (46, "bf16"),    # a chip of the sharded catalog
    (184, "int8"),   # the whole marketplace stored int8
]


@pytest.mark.parametrize("nt,mode", SERVED_SINGLES)
def test_a_singles_step_reads_each_side_array_once(one_chip, monkeypatch, nt, mode):
    """The served B = 1 programs: after the score the row scales and
    the row ids are each an operand of exactly ONE fusion of the loop —
    one of two results, the ``[1,2048,128]`` scores and their ``[2048]``
    maxima, scale and guard computed once — where the step without its
    barrier (PR 42's) hands each to two; the store is a bare
    update-slice of that fusion's result; and the program's temporaries
    stay within 1 MB of that step's (the stored scores: 193 MB over 184
    int8 tiles, in the compiler's own memory space over 36 or 46)."""
    served = _compiled(one_chip, 1, nt, 64, mode)
    text = served.as_text()
    want = {"s32": 1} if mode == "bf16" else {"s32": 1, "f32": 1}
    assert _readers(text, nt) == want
    both = re.findall(
        r"= \((f32\[2048\])\S*, (f32\[1,2048,128\])\S*\) fusion\(", _loop_text(text)
    )
    assert both == [("f32[2048]", "f32[1,2048,128]")]
    store = re.search(
        rf"= f32\[{nt},1,2048,128\]\S* fusion\(([^)]*)\), kind=", _loop_text(text)
    ).group(1).split(", ")
    assert len(store) == 3  # the stacked scores, the step, the fusion's result
    twice = _compiled(one_chip, 1, nt, 64, mode,
                      program=_without_the_barrier(monkeypatch))
    assert _readers(twice.as_text(), nt) == {d: 2 for d in want}
    temp, was = (c.memory_analysis().temp_size_in_bytes for c in (served, twice))
    assert abs(temp - was) <= 1 << 20
    assert (temp >= nt * TILE * 4) == (mode == "int8")


@pytest.mark.parametrize("nt,mode,parents", [(36, "bf16", 5), (184, "int8", 5)])
def test_a_singles_step_has_no_more_passes_than_the_flat_forms(
        one_chip, monkeypatch, nt, mode, parents):
    """B = 1: the score, the three rows' sum, ONE pass that scales and
    guards the sum and gives both the scores and their maxima, and the
    two stores, each a bare update-slice — no more passes than PR 42's
    five (``parents``: there the maxima and the store each scaled and
    guarded the sum for themselves, as the same scan over ``[NT, T]``
    side arrays still does); that ONE of them reads a side array where
    two did is ``test_a_singles_step_reads_each_side_array_once``."""
    lanes = _passes(_compiled(one_chip, 1, nt, 64, mode).as_text())
    flat = _passes(_compiled(one_chip, 1, nt, 64, mode, (nt, TILE)).as_text())
    assert len(flat) == parents and len(lanes) <= len(flat), (lanes, flat)
    twice = _compiled(one_chip, 1, nt, 64, mode,
                      program=_without_the_barrier(monkeypatch)).as_text()
    assert len(_passes(twice)) == parents


def test_the_masked_scans_side_array_is_dense_too(one_chip):
    """Both Taobao cells' masked single (16 tiles, rank 128): the ids'
    slice is the dense block; the rules' vectors stay the compiler's."""
    from predictionio_tpu.ops.topk import Rules

    nt, d = 16, 128
    arg = _on(one_chip)
    rules = Rules(
        arg((nt * TILE,), jnp.uint8), (arg((nt * TILE,), jnp.int32),),
        arg((1, 1), jnp.int32), arg((1,), jnp.bool_), arg((1, 128), jnp.int32),
    )
    text = retrieval._coarse_topk_masked.lower(
        arg((1, d), jnp.float32), arg((nt, TILE, d), jnp.bfloat16), None,
        arg(retrieval.side_shape(nt, TILE), jnp.int32), rules, k=KP, mode="bf16",
    ).compile().as_text()
    assert re.search(
        r"= s32\[1,2048,128\]{2,1,0:T\(8,128\)} dynamic-slice\(", text
    )
    assert f"[{nt},262144]{{1,0:T(8,128)}} parameter(" not in text
    assert f"s32[{nt},2048,128]{{2,1,0:T(8,128)}} parameter(" in text


def test_the_four_chip_chain_runs_the_same_step(one_chip):
    """``_sharded_topk`` for the sharded cell's four chips (46 tiles a
    chip, one query): each device's ids are ``s32[46,2048,128]``, its
    loop body the one-chip single's — the three rows' sum, no
    ``compare_select_fusion`` over a strided row — and the program's
    collectives are all-gathers: the broadcast of shard 0's queries in
    front (PR 49) and the answers' behind."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from predictionio_tpu.parallel import shard_topk

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("data",))
    n, nt, d = 4, 46, 64
    split = _on(NamedSharding(mesh, P("data")))
    text = shard_topk._sharded_topk.lower(
        split((n, 1, d), jnp.float32),
        split((n * nt * TILE, d), jnp.float32),
        split((n * nt, TILE, d), jnp.bfloat16),
        split((n * nt, *retrieval.side_shape(nt, TILE)[1:]), jnp.int32),
        r=12_047_500, kp=KP, k=16, mode="bf16", mesh=mesh, axis="data",
    ).compile().as_text()
    assert f"s32[{nt},2048,128]{{2,1,0:T(8,128)}} parameter(" in text
    assert f"[{nt},262144]" not in text
    ops = _passes(text)
    assert len(ops) == 5 and not [o for o in ops if o[1] in ("copy", "sort")], ops
    assert set(re.findall(r"(all-gather|all-reduce|all-to-all|collective-permute)", text)) \
        == {"all-gather"}


@pytest.mark.parametrize("b", [1, 16])
def test_the_masked_four_chip_chain_fits_a_chip_beside_its_catalog(one_chip, b):
    """``_sharded_topk_masked`` for the sharded storefront's four chips (46
    tiles a chip; the rules' vectors sharded like the rows, the dispatch
    one packed buffer on shard 0, handed round): it compiles, its
    collectives are all-gathers, every rule vector is read where it lies (no copy of a
    [stored] vector), and its temporaries — the stored scores and the
    [46, B, 2^18] mask — leave a 16 GB chip's 4.8 GB of catalog room."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from predictionio_tpu.parallel import shard_topk

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("data",))
    n, nt, d = 4, 46, 64
    split = _on(NamedSharding(mesh, P("data")))
    layout = retrieval.Layout(d, 1, 128)
    compiled = shard_topk._sharded_topk_masked.lower(
        split((n, b, sum(layout[:3]) + 1), jnp.int32), None,
        split((n * nt * TILE, d), jnp.float32),
        split((n * nt, TILE, d), jnp.bfloat16),
        split((n * nt, *retrieval.side_shape(nt, TILE)[1:]), jnp.int32),
        split((n * nt * TILE,), jnp.uint8), (split((n * nt * TILE,), jnp.int32),),
        r=12_047_500, kp=KP, k=16, mode="bf16", mesh=mesh, axis="data",
        layout=layout,
    ).compile()
    text = compiled.as_text()
    assert set(re.findall(r"(all-gather|all-reduce|all-to-all|collective-permute)", text)) \
        == {"all-gather"}
    resident = nt * TILE * (d * 6 + 4 + 4 + 1)
    assert compiled.memory_analysis().temp_size_in_bytes + resident < 12e9


@pytest.mark.parametrize("b,k", [(8, 8), (16, 32)])
def test_the_folds_solve_gathers_from_the_resident_table_in_place(one_chip, b, k):
    """PR 45: the fold-in's solve program over the int8 catalog's resident
    pair (48.19 M x 64 values, their scales) needs no temporary to speak
    of. Gathered plainly (``table[ids]``) the compiler re-lays the whole
    table a call: 6.2 GB. And the update of the resident user table is a
    copy of that table, not of anything else."""
    from predictionio_tpu.realtime import foldin

    arg = _on(one_chip)
    table = (arg((48_190_000, 64), jnp.int8), arg((48_190_000,), jnp.float32))
    solve = foldin._solve_rows.lower(
        table, arg((b, k), jnp.int32), arg((b, k), jnp.float32),
        arg((b, k), jnp.float32), reg=0.05, weighted_reg=True,
    ).compile()
    assert solve.memory_analysis().temp_size_in_bytes < 1 << 20
    users = (arg((1 << 20, 64), jnp.int8), arg((1 << 20,), jnp.float32))
    patch = retrieval.patch_rows.lower(
        users, arg((b,), jnp.int32), (arg((b, 64), jnp.int8), arg((b,), jnp.float32)),
    ).compile().memory_analysis()
    assert (1 << 20) * 68 <= patch.output_size_in_bytes < (1 << 20) * 69
    assert patch.temp_size_in_bytes <= 2 * (1 << 20) * 68
