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


def _compiled(chip, b, nt, d, mode="bf16", sides=None):
    """``_coarse_topk`` for ``b`` queries over ``nt`` tiles, the side
    arrays as a catalog stores them (``sides``: another shape)."""
    arg = _on(chip)
    sides = sides or retrieval.side_shape(nt, TILE)
    return retrieval._coarse_topk.lower(
        arg((b, d), jnp.float32),
        arg((nt, TILE, d), jnp.bfloat16 if mode == "bf16" else jnp.int8),
        None if mode == "bf16" else arg(sides, jnp.float32),
        arg(sides, jnp.int32), k=KP, mode=mode,
    ).compile()


def _loop_body(text):
    """The instructions of the scan's ``while`` body in a compiled
    module's text: [(result shape as text, opcode)]."""
    name = re.search(r"while\(.*?body=%?([\w.\-]+)", text).group(1)
    start = text.index(f"\n%{name} ")
    body = text[start: text.index("\n}\n", start)]
    return re.findall(r"= (\S+?)\{[^ ]* (\w[\w\-]*)\(", body)


@pytest.mark.parametrize("b,nt,d", [
    (8, 36, 64), (16, 36, 64),     # yambda: the saturated cell's two batches
    (16, 46, 64),                  # a chip of the sharded catalog
    (16, 16, 128),                 # both Taobao configurations' tiles
])
def test_a_batchs_step_selects_nothing_and_stores_its_scores(one_chip, b, nt, d):
    """The compiled loop body of a served batch holds no ``sort`` (no
    selection, no merge), and the program's temporaries are the stored
    scores (B x NT x T x 4 bytes) and a little: what the bound of
    ``scan_select`` reckons with."""
    assert retrieval.scan_select(b, nt, TILE, KP, d) == "deferred"
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


def test_a_batch_beyond_the_bound_stores_nothing(one_chip):
    """64 queries at rank 64 (2.4 GB of scores against 1.2 GB of tiles):
    the per-tile body, whose temporaries are the merge's."""
    assert retrieval.scan_select(64, 36, TILE, KP, 64) == "two_level"
    compiled = _compiled(one_chip, 64, 36, 64)
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20
    assert "f32[36," not in compiled.as_text()  # no scores stacked by tile


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


@pytest.mark.parametrize("nt,mode,parents", [(36, "bf16", 5), (184, "int8", 5)])
def test_a_singles_step_has_no_more_passes_than_the_flat_forms(
        one_chip, nt, mode, parents):
    """B = 1: the score, the three rows' sum, the maxima and the two
    stores — five passes a step, as many as the same scan compiled over
    ``[NT, T]`` side arrays (the parent's program: ``parents``), with the
    scale and the guard fused into the maxima and the store."""
    lanes = _passes(_compiled(one_chip, 1, nt, 64, mode).as_text())
    flat = _passes(_compiled(one_chip, 1, nt, 64, mode, (nt, TILE)).as_text())
    assert len(flat) == parents and len(lanes) <= len(flat), (lanes, flat)


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
    ``compare_select_fusion`` over a strided row — and the program's one
    collective is the all-gather."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from predictionio_tpu.parallel import shard_topk

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("data",))
    n, nt, d = 4, 46, 64
    whole, split = _on(NamedSharding(mesh, P())), _on(NamedSharding(mesh, P("data")))
    text = shard_topk._sharded_topk.lower(
        whole((1, d), jnp.float32),
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
