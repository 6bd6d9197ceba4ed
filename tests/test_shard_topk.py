"""Stationary-shard retrieval vs the plain reference and the one-chip chain.

Runs on the virtual 8-device CPU mesh (conftest), the stand-in for a
four-chip host — the analog of the reference testing "distributed"
behavior on Spark local[4] (core/src/test/scala/.../workflow/
BaseTest.scala:31-92). The plain reference is NumPy f32 over the whole
table and knows nothing of shards.
"""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.topk import Rules
from predictionio_tpu.parallel import shard_topk
from predictionio_tpu.parallel.mesh import make_mesh, parse_axes, serving_mesh
from predictionio_tpu.parallel.shard_topk import ShardedCatalog

PAST = 1 << 30  # rows of a catalog whose stored scores are past retrieval._UNCUT


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh([("data", 4)])


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh([("data", 8)])


@pytest.fixture()
def two_stage(monkeypatch):
    """Toy sizes that still go through shortlist -> rescore: threshold
    1,000 rows, tiles of 512, no recall probe unless a test asks."""
    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "1000")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", "512")
    monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")


def _tables(items, d=16, users=24, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((users, d)).astype(np.float32),
            rng.standard_normal((items, d)).astype(np.float32))


def _reference(q, v, k):
    """([B, k] scores, [B, k] ids) of the whole catalog in plain f32."""
    sc = q.astype(np.float32) @ v.astype(np.float32).T
    ids = np.argsort(-sc, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(sc, ids, axis=1), ids


def _served(cat, U, uix, num_items, k):
    return retrieval.top_k(
        retrieval.UserRows(np.asarray(uix), None, lambda ix: U[ix]),
        cat, num_items, None, k,
    )


class TestShardedChain:
    @pytest.mark.parametrize("batch", [1, 2, 8])
    def test_matches_the_plain_reference(self, mesh4, two_stage, batch):
        U, V = _tables(9000)
        cat = ShardedCatalog(V, mesh4)
        s, ids = _served(cat, U, range(batch), len(V), 16)
        rs, ri = _reference(U[:batch], V, 16)
        np.testing.assert_array_equal(ids, ri)
        np.testing.assert_allclose(s, rs, rtol=0, atol=2e-6 * np.abs(rs).max())

    @pytest.mark.parametrize("batch", [1, 2, 8])
    def test_matches_the_one_chip_chain(self, mesh4, two_stage, batch):
        U, V = _tables(9000, seed=1)
        uix = np.arange(batch)
        s, ids = _served(ShardedCatalog(V, mesh4), U, uix, len(V), 16)
        one = retrieval.top_k(
            retrieval.UserRows(uix, jax.numpy.asarray(U), lambda ix: U[ix]),
            jax.numpy.asarray(V), len(V), retrieval.CoarseCatalog(V), 16,
        )
        np.testing.assert_array_equal(ids, one[1])
        np.testing.assert_allclose(s, one[0], rtol=0, atol=4e-6)

    @pytest.mark.parametrize("batch,chunks", [(1, 1), (2, 1), (4, 1), (8, 2)])
    @pytest.mark.parametrize("items", [4 * 2 * 8192, 70_001])
    def test_tiles_wide_enough_to_split_give_the_one_chip_answer(
        self, mesh4, two_stage, monkeypatch, items, batch, chunks
    ):
        """Tiles of 8,192 rows, two or three a shard: a scan under
        ``shard_map`` selects once after each shard's loop — a
        single's, a pair's, a batch of four's; eight queries of this
        rank would store more than half of what a step reads and are
        two chunks of four on every shard, in the one program. All
        serve the one-chip chain's answer and the plain reference's."""
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "8192")
        U, V = _tables(items, seed=12)
        cat = ShardedCatalog(V, mesh4)
        kp = retrieval.two_stage_k(16, len(V))
        assert cat.tile == 8192 and cat.tiles_per_shard == -(-items // 4 // 8192)
        assert cat._ids.shape == (4 * cat.tiles_per_shard, 64, 128)
        assert retrieval.select_group(cat.tile, kp, cat.tiles_per_shard)
        assert batch // retrieval.scan_chunk(batch, cat.dim, cat.mode, PAST) == chunks
        uix = np.arange(batch)
        before = retrieval.stats_block()
        s, ids = _served(cat, U, uix, len(V), 16)
        after = retrieval.stats_block()
        assert after["host_reads"] == before["host_reads"] + 1
        assert after["uploads"] == before["uploads"] + 1
        rs, ri = _reference(U[:batch], V, 16)
        np.testing.assert_array_equal(ids, ri)
        np.testing.assert_allclose(s, rs, rtol=0, atol=4e-6 * np.abs(rs).max())
        one = retrieval.top_k(
            retrieval.UserRows(uix, jax.numpy.asarray(U), lambda ix: U[ix]),
            jax.numpy.asarray(V), len(V), retrieval.CoarseCatalog(V), 16,
        )
        np.testing.assert_array_equal(ids, one[1])
        np.testing.assert_allclose(s, one[0], rtol=0, atol=4e-6)

    def test_the_sharded_singles_scan_body_selects_nothing(self, mesh4):
        """The traced sharded program at B = 1, B = 2 and B = 16 (rank
        16: beyond the bound, four chunks): no ``sort`` / ``top_k``
        inside the loop over the tiles; the selections follow it."""
        nt, t, d = 2, 8192, 16
        shapes = lambda b: (  # noqa: E731
            jax.ShapeDtypeStruct((4, b, d), np.float32),
            jax.ShapeDtypeStruct((4 * nt * t, d), np.float32),
            jax.ShapeDtypeStruct((4 * nt, t, d), jax.numpy.bfloat16),
            jax.ShapeDtypeStruct((4 * nt, t), np.int32),
        )

        def selections(jp, in_loop=False):
            """(``sort`` / ``top_k`` equations under the scan over the
            ``nt`` tiles, those anywhere else)."""
            inside = outside = 0
            for e in jp.eqns:
                hit = e.primitive.name in ("sort", "top_k")
                inside, outside = inside + (hit and in_loop), outside + (
                    hit and not in_loop)
                tiles = (e.primitive.name == "scan"
                         and e.params["length"] == nt)
                for v in e.params.values():
                    for sub in v if isinstance(v, (list, tuple)) else (v,):
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            i, o = selections(inner, in_loop or tiles)
                            inside, outside = inside + i, outside + o
            return inside, outside

        def loop_sorts(b):
            return selections(jax.make_jaxpr(
                lambda *a: shard_topk._sharded_topk(
                    *a, r=nt * t, kp=128, k=16, mode="bf16", mesh=mesh4,
                    axis="data",
                )
            )(*shapes(b)).jaxpr)

        # after the loop: the maxima's, the candidates', the rescore's
        # and the merge's
        assert loop_sorts(1) == (0, 4)
        assert loop_sorts(2) == (0, 4)
        assert retrieval.scan_chunk(16, d, "bf16", PAST) == 4
        assert loop_sorts(16) == (0, 2 * 4 + 2)  # a loop and two a chunk

    @pytest.mark.parametrize("batch,barriers", [(1, 1), (2, 0)])
    def test_the_four_chip_chain_runs_the_one_chip_step(self, mesh4, batch, barriers):
        """Each chip of the sharded chain runs ``_coarse_scan``'s own
        step: a single's scores and their group maxima are the two
        results of one computation (one ``optimization_barrier`` in the
        shard's loop, PR 43), a pair's ride its dot as they did."""
        nt, t, d = 2, 8192, 16
        jaxpr = jax.make_jaxpr(
            lambda *a: shard_topk._sharded_topk(
                *a, r=nt * t, kp=128, k=16, mode="bf16", mesh=mesh4, axis="data",
            )
        )(
            jax.ShapeDtypeStruct((4, batch, d), np.float32),
            jax.ShapeDtypeStruct((4 * nt * t, d), np.float32),
            jax.ShapeDtypeStruct((4 * nt, t, d), jax.numpy.bfloat16),
            jax.ShapeDtypeStruct((4 * nt, t // 128, 128), np.int32),
        )
        assert retrieval.score_form(batch, d) == ("dot" if batch == 1 else "rows")
        assert str(jaxpr).count("optimization_barrier") == barriers

    def test_eight_shards(self, mesh8, two_stage):
        U, V = _tables(9000, seed=2)
        s, ids = _served(ShardedCatalog(V, mesh8), U, [3, 4, 5], len(V), 8)
        rs, ri = _reference(U[3:6], V, 8)
        np.testing.assert_array_equal(ids, ri)
        np.testing.assert_allclose(s, rs, rtol=0, atol=4e-6)

    def test_rows_the_mesh_does_not_divide_pad_with_minus_one(self, mesh4, two_stage):
        """4 does not divide 9,001 rows, nor do the tiles fill: the
        padding carries id -1 and is never served, even where k asks for
        more than a small catalog has."""
        U, V = _tables(9001, seed=3)
        cat = ShardedCatalog(V, mesh4)
        assert cat.rows_per_shard * 4 > len(V)
        assert int((np.asarray(cat._ids) >= 0).sum()) == len(V)
        s, ids = _served(cat, U, range(4), len(V), 16)
        assert ids.min() >= 0 and ids.max() < len(V)
        np.testing.assert_array_equal(ids, _reference(U[:4], V, 16)[1])
        tiny = ShardedCatalog(V[:6], mesh4)
        s, ids = tiny.exact_top_k(U[:2], 16)
        assert (np.sort(ids, axis=1)[:, -6:] == np.arange(6)).all()
        assert (ids[:, 6:] == -1).all()

    @pytest.mark.parametrize("tile,lanes", [(8192, 128), (200, 200)])
    def test_a_shards_ids_lie_as_the_one_chip_catalogs(
        self, mesh4, monkeypatch, tile, lanes
    ):
        """[tiles, T/128, 128] a shard (``retrieval.side_shape``; the
        whole tile where 128 lanes do not divide it), split over the
        mesh on the leading axis: each device holds its own tiles' ids,
        global, -1 in the padding."""
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", str(tile))
        _, V = _tables(4 * 2 * tile - 3 * tile // 2, seed=4)
        cat = ShardedCatalog(V, mesh4)
        nt = cat.tiles_per_shard
        assert cat.tile == tile and nt == 2
        assert cat._ids.shape == (4 * nt, tile // lanes, lanes)
        assert cat._ids.shape[1:] == retrieval.side_shape(nt, tile)[1:]
        for i, shard in enumerate(cat._ids.addressable_shards):
            got = np.asarray(shard.data).reshape(-1)
            assert shard.data.shape == retrieval.side_shape(nt, tile)
            lo = i * cat.rows_per_shard
            n = min(cat.rows_per_shard, len(V) - lo)
            np.testing.assert_array_equal(got[:n], np.arange(lo, lo + n))
            assert (got[n:] == -1).all()

    def test_a_shortlist_wider_than_a_tile_clamps(self, mesh4, two_stage, monkeypatch):
        """k' = 8 * pow2(k) = 1,024 against tiles of 128 rows: a shard
        can shortlist a tile's width at most, and the answer is still
        the reference's."""
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        U, V = _tables(4000, seed=4)
        cat = ShardedCatalog(V, mesh4)
        assert cat.tile == 128 and retrieval.two_stage_k(128, len(V)) == 128
        s, ids = _served(cat, U, range(2), len(V), 128)
        assert ids.shape == (2, 128)
        np.testing.assert_array_equal(ids, _reference(U[:2], V, 128)[1])

    def test_the_shards_answers_merged_are_the_uncut_reference(self, mesh4):
        """The shares add up: each shard's own top-k, taken from its rows
        alone by the plain reference, merged by the program's merge, is
        the whole catalog's top-k."""
        U, V = _tables(1203, seed=5)
        k, r = 8, -(-len(V) // 4)
        parts = [_reference(U[:5], V[i * r:(i + 1) * r], k) for i in range(4)]
        all_s = np.stack([p[0] for p in parts])
        all_i = np.stack([p[1] + i * r for i, p in enumerate(parts)]).astype(np.int32)
        s, ids = shard_topk._merge(jax.numpy.asarray(all_s), jax.numpy.asarray(all_i), k)
        rs, ri = _reference(U[:5], V, k)
        np.testing.assert_array_equal(np.asarray(ids), ri)
        np.testing.assert_array_equal(np.asarray(s), rs)

    def test_one_read_a_dispatch_and_the_counters(self, mesh4, two_stage):
        U, V = _tables(9000, seed=6)
        cat = ShardedCatalog(V, mesh4)
        before = retrieval.stats_block()
        _served(cat, U, range(3), len(V), 16)
        after = retrieval.stats_block()
        assert after["host_reads"] - before["host_reads"] == 1
        # the vectors and nothing else: the chain without rules
        assert after["uploads"] - before["uploads"] == 1
        assert after["sharded_queries"] - before["sharded_queries"] == 3
        assert after["two_stage_queries"] == before["two_stage_queries"]
        # 4 shards x 4 padded rows x k 16 x 8 B
        assert after["shard_gather_bytes"] - before["shard_gather_bytes"] == 4 * 4 * 16 * 8
        assert after["shards"] == 4

    def test_the_probe_runs_the_sharded_exact_program(self, mesh4, two_stage, monkeypatch):
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "1")
        U, V = _tables(9000, seed=7)
        cat = ShardedCatalog(V, mesh4)
        probes = retrieval._m_probes.value()
        reads = retrieval._m_host_reads.value()
        _served(cat, U, range(2), len(V), 16)
        assert retrieval._m_probes.value() == probes + 1
        assert retrieval._m_probe_recall.value() == 1.0
        assert retrieval._m_host_reads.value() == reads + 2  # the answer, the probe

    def test_under_the_threshold_the_exact_program_serves(self, mesh4):
        U, V = _tables(300, seed=8)
        s, ids = _served(ShardedCatalog(V, mesh4), U, range(5), len(V), 4)
        rs, ri = _reference(U[:5], V, 4)
        np.testing.assert_array_equal(ids, ri)
        np.testing.assert_allclose(s, rs, rtol=0, atol=4e-6)

    def test_varied_traffic_reuses_compiled_programs(self, mesh4, two_stage):
        U, V = _tables(9000, seed=9)
        cat = ShardedCatalog(V, mesh4)
        _served(cat, U, range(3), len(V), 16)  # bucket 4
        before = shard_topk._sharded_topk._cache_size()
        _served(cat, U, range(4), len(V), 16)
        _served(cat, U, [7, 8, 9], len(V), 16)
        assert shard_topk._sharded_topk._cache_size() == before

    def test_int8_pairs_stage_dequantized(self, mesh4, two_stage):
        rng = np.random.default_rng(10)
        vq = rng.integers(-127, 128, (2000, 8)).astype(np.int8)
        vs = rng.uniform(0.01, 0.02, 2000).astype(np.float32)
        U = rng.standard_normal((4, 8)).astype(np.float32)
        cat = ShardedCatalog((vq, vs), mesh4)
        s, ids = _served(cat, U, range(4), 2000, 8)
        rs, ri = _reference(U, vq.astype(np.float32) * vs[:, None], 8)
        np.testing.assert_array_equal(ids, ri)
        np.testing.assert_allclose(s, rs, rtol=0, atol=2e-6)

    def test_a_sum_of_rows_under_rules_is_refused_by_name(self, mesh4, two_stage):
        """(``Vectors`` under rules are served: tests/test_shard_rules.py.)"""
        U, V = _tables(2000, seed=11)
        rules = Rules(avail=None, cats=(), qcat=None, has_cat=None, ex=None)
        query = retrieval.SumRows(
            np.zeros((1, 8), np.int32), np.ones((1, 8), np.float32),
            lambda ixs, weights: V[ixs[:, 0]], rules)
        with pytest.raises(ValueError, match="SumRows query under rules"):
            retrieval.top_k(query, ShardedCatalog(V, mesh4), len(V), None, 8)

    def test_a_mesh_of_two_axes_is_refused(self):
        with pytest.raises(ValueError, match="1-D mesh"):
            ShardedCatalog(_tables(64)[1], make_mesh([("data", 2), ("model", 2)]))


MESHES = (1, 2, 4)


@pytest.fixture(scope="module")
def catalogs():
    """{shards: (a 3,001-row catalog over that many devices, U, V)}."""
    U, V = _tables(3001, seed=13)
    return {n: (ShardedCatalog(V, make_mesh([("data", n)])), U, V) for n in MESHES}


def _host_batch(kind, b, rng):
    """(host array, dtype, rows it is padded to) of the three arrays a
    dispatch sends: f32 vectors (-0.0, a denormal, an infinity among
    them), ``retrieval.pack``'s buffer (f32 bit patterns beside negative
    ids) and a ``whiteList`` batch's candidate ids."""
    vecs = rng.standard_normal((b, 16)).astype(np.float32)
    vecs[0, :3] = [-0.0, 1e-42, -np.inf]
    if kind == "vectors":
        return vecs, np.float32, retrieval._pow2(b)
    if kind == "packed":
        ex = rng.integers(0, 3001, (b, 8)).astype(np.int32)
        ex[:, 5:] = -1
        rules = Rules(None, (), np.full((b, 1), -2, np.int32), np.arange(b) % 2 == 0, ex)
        return retrieval.pack(vecs, rules)[0], np.int32, 0
    cand = rng.integers(0, 3001, (b, 24)).astype(np.int32)
    cand[:, 20:] = -1
    return cand, np.int32, retrieval._pow2(b)


class TestOneCopyADispatch:
    """A host array reaches the shards by ONE route: a copy to the mesh's
    first device, the stitch, and the programs' first statement."""

    @pytest.mark.parametrize("kind", ["vectors", "packed", "candidates"])
    @pytest.mark.parametrize("batch", [1, 3, 16])
    @pytest.mark.parametrize("shards", MESHES)
    def test_every_shard_holds_what_a_copy_of_its_own_would_give_it(
            self, catalogs, shards, batch, kind):
        """Bit for bit, on every shard: what ``_from_first`` leaves there
        against a replicated ``device_put`` of the same host array."""
        cat = catalogs[shards][0]
        a, dtype, rows = _host_batch(kind, batch, np.random.default_rng(batch))
        uploads = retrieval.stats_block()["uploads"]
        got = cat.put_replicated(a, dtype, rows)
        assert retrieval.stats_block()["uploads"] == uploads + 1
        bp = retrieval._pow2(batch)
        assert got.shape == (shards, bp, a.shape[1]) and got.dtype == dtype
        assert got.sharding.is_equivalent_to(cat._ids.sharding, 3)
        blocks = [np.asarray(sh.data) for sh in got.addressable_shards]
        assert all(not blk.any() for blk in blocks[1:])  # the resident zeros
        handed = jax.jit(jax.shard_map(
            lambda x: shard_topk._from_first(x, cat.axis)[None], mesh=cat.mesh,
            in_specs=P(cat.axis), out_specs=P(cat.axis), check_vma=False,
        ))(got)
        want = jax.device_put(
            retrieval._pad_rows(np.ascontiguousarray(a, dtype), rows),
            NamedSharding(cat.mesh, P()),
        )
        assert len(handed.addressable_shards) == shards
        for mine, own in zip(handed.addressable_shards, want.addressable_shards):
            assert mine.device == own.device
            np.testing.assert_array_equal(
                np.asarray(mine.data)[0].view(np.uint32),
                np.asarray(own.data).view(np.uint32))

    @pytest.mark.parametrize("batch", [1, 3, 16])
    @pytest.mark.parametrize("shards", MESHES)
    def test_both_programs_answer_as_the_plain_reference(
            self, catalogs, two_stage, shards, batch):
        """The two-stage and the exact program on meshes of one, two and
        four devices: the reference's ids, its scores to f32 rounding —
        and the same answer from a batch whose EVERY shard was given a
        copy of its own (what the replicated route held), bit for bit."""
        cat, U, V = catalogs[shards]
        rs, ri = _reference(U[:batch], V, 16)
        s, ids = _served(cat, U, range(batch), len(V), 16)
        np.testing.assert_array_equal(ids, ri)
        np.testing.assert_allclose(s, rs, rtol=0, atol=4e-6 * np.abs(rs).max())
        es, eids = cat.exact_top_k(U[:batch], 16)
        np.testing.assert_array_equal(eids, ri)
        np.testing.assert_allclose(es, rs, rtol=0, atol=4e-6 * np.abs(rs).max())
        padded = retrieval._pad_rows(U[:batch], retrieval._pow2(batch))[None]
        copies = jax.make_array_from_single_device_arrays(
            (shards, *padded.shape[1:]), cat._split,
            [jax.device_put(padded, d) for d in cat._devices])
        kp = retrieval.two_stage_k(16, len(V))
        for got, out in ((s, cat.launch(copies, kp, 16)),
                         (es, cat.launch_exact(copies, 16))):
            np.testing.assert_array_equal(
                got.view(np.uint32),
                np.asarray(out[0])[:batch].view(np.uint32))

    @pytest.mark.parametrize("shards", MESHES)
    def test_one_copy_a_dispatch_and_the_zero_blocks_once_a_shape(
            self, two_stage, monkeypatch, shards):
        """``shard_h2d_copies``: the first dispatch of a bucket writes
        every shard's block (one copy, shards - 1 zero blocks), every
        later one the copy alone; the recall probe slices the dispatch's
        device batch and writes none."""
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "1")
        U, V = _tables(3001, seed=14)
        cat = ShardedCatalog(V, make_mesh([("data", shards)]))

        def dispatch(uix):
            before = retrieval.stats_block()
            _served(cat, U, uix, len(V), 16)
            after = retrieval.stats_block()
            assert after["uploads"] - before["uploads"] == 1
            assert after["host_reads"] - before["host_reads"] == 2  # + the probe
            assert after["probes"] - before["probes"] == 1
            return after["shard_h2d_copies"] - before["shard_h2d_copies"]

        assert dispatch(range(3)) == shards  # bucket 4: the copy + the zeros
        assert dispatch(range(4)) == 1
        assert dispatch([5, 6, 7]) == 1
        assert len(cat._zeros) == 1
        assert dispatch(range(1)) == shards  # another bucket
        assert dispatch([9]) == 1
        assert {k[0] for k in cat._zeros} == {(1, 4, 16), (1, 1, 16)}
        assert all(len(z) == shards - 1 for z in cat._zeros.values())


class TestServingMesh:
    def test_default_is_every_device(self, monkeypatch):
        monkeypatch.delenv("PIO_MESH", raising=False)
        assert dict(serving_mesh().shape) == {"data": len(jax.devices())}

    def test_pio_mesh_names_the_shards(self, monkeypatch):
        monkeypatch.setenv("PIO_MESH", "data=4")
        assert dict(serving_mesh().shape) == {"data": 4}

    @pytest.mark.parametrize("spec", ["model=4", "data=2,model=2"])
    def test_another_axis_is_refused(self, monkeypatch, spec):
        monkeypatch.setenv("PIO_MESH", spec)
        with pytest.raises(ValueError, match="one axis"):
            serving_mesh()

    @pytest.mark.parametrize("spec", ["data", "data=0", "=4", "data=x", "a=-1,b=-1"])
    def test_a_bad_spec_is_refused(self, spec):
        with pytest.raises(ValueError):
            parse_axes(spec)

    def test_deploy_refuses_a_bad_mesh_before_anything_loads(self):
        from predictionio_tpu.cli.main import _parse_mesh

        with pytest.raises(SystemExit, match="--mesh"):
            _parse_mesh("data=none")
        assert _parse_mesh("data=4") == [("data", 4)]
