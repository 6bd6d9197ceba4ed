"""EntityMap: id-indexed entity view (reference EntityMap.scala:69)."""

import pytest

from predictionio_tpu.data.propertymap import EntityMap


@pytest.fixture()
def emap():
    return EntityMap({"u3": {"a": 3}, "u1": {"a": 1}, "u2": {"a": 2}})


class TestEntityMap:
    def test_mapping_protocol(self, emap):
        assert len(emap) == 3
        assert "u1" in emap and "u9" not in emap
        assert emap["u2"] == {"a": 2}
        assert sorted(emap) == ["u1", "u2", "u3"]

    def test_index_stable_and_insertion_order_independent(self):
        a = EntityMap({"u3": 3, "u1": 1, "u2": 2})
        b = EntityMap({"u1": 1, "u2": 2, "u3": 3})
        # indices are assigned over sorted ids, so two maps built from
        # the same entities in different orders agree — factor-matrix
        # rows stay aligned across rebuilds
        for eid in ("u1", "u2", "u3"):
            assert a.index_of(eid) == b.index_of(eid)
        assert sorted(a.index_of(e) for e in a) == [0, 1, 2]

    def test_inverse_roundtrip(self, emap):
        for eid in emap:
            assert emap.entity_of(emap.index_of(eid)) == eid
        with pytest.raises(KeyError):
            emap.index_of("missing")

    def test_id_index_is_bimap(self, emap):
        bm = emap.id_index
        assert len(bm) == 3
        assert bm.inverse[bm["u1"]] == "u1"


class TestIdIndexLookups:
    """``id_index`` is the BiMap a model file stores: its vectorised
    ``index_of`` and the same look-ups over the stored (encoded) form."""

    @pytest.mark.parametrize("keys,want", [
        (["u1", "u3"], [0, 2]),
        (["u2"], [1]),
        ([], []),
        (["u9", "u1", "", "u"], [-1, 0, -1, -1]),
        (["u1", 7, None], [0, -1, -1]),
    ], ids=["list", "single", "empty", "absent", "non-strings"])
    def test_index_of(self, emap, keys, want):
        from predictionio_tpu.models import modelfile

        bm = emap.id_index
        got = bm.index_of(keys)
        assert got.dtype.kind == "i" and got.tolist() == want
        stored = modelfile._LazyDenseBiMap(*modelfile._encode_ids(list(bm.inverse[i] for i in range(len(bm)))))
        assert stored.index_of(keys).tolist() == want
        assert [stored.get(k) for k in keys] == [bm.get(k) for k in keys]
        assert [k in stored for k in keys] == [k in bm for k in keys]
        assert stored._fwd is None
