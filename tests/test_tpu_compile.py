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


def _compiled(chip, b, nt, d):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    return retrieval._coarse_topk.lower(
        arg((b, d), jnp.float32), arg((nt, TILE, d), jnp.bfloat16), None,
        arg((nt, TILE), jnp.int32), k=KP, mode="bf16",
    ).compile()


def _loop_body(text):
    """The instructions of the scan's ``while`` body in a compiled
    module's text: [(opcode, result shape as text)]."""
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

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    resident = Rules(
        arg((nt * TILE,), jnp.uint8), (arg((nt * TILE,), jnp.int32),),
        None, None, None,
    )
    separate = resident._replace(
        qcat=arg((b, 1), jnp.int32), has_cat=arg((b,), jnp.bool_),
        ex=arg((b, e), jnp.int32),
    )
    layout = retrieval.Layout(d, 1, e)
    catalog = (arg((nt, TILE, d), jnp.bfloat16), None, arg((nt, TILE), jnp.int32))
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
