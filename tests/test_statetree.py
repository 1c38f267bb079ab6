"""The committed state tree (cometbft_tpu/statetree/): versioned
reads, existence + non-inclusion proofs and their tamper matrix,
height pruning with cache pins, crash/restart root recovery, and
byte-identical statesync restore (docs/state_tree.md)."""
import json

import pytest

from cometbft_tpu.abci import types as abci
from cometbft_tpu.abci.kvstore import KVStoreApplication, _zigzag_varint
from cometbft_tpu.crypto import merkle
from cometbft_tpu.db import MemDB, SQLiteDB
from cometbft_tpu.statetree import (
    StateTree, build_proof_envelope, verify_proof_envelope,
)

from tests.test_abci import _drive_blocks, run


def _tree(db=None) -> StateTree:
    return StateTree(db if db is not None else MemDB())


def _commit_pairs(tree, version, pairs):
    for k, v in pairs:
        tree.set(k, v)
    return tree.commit(version)


# ---------------------------------------------------------------------------
# versioned reads / commit discipline


class TestVersionedTree:
    def test_versioned_reads_and_roots(self):
        t = _tree()
        r1 = _commit_pairs(t, 1, [(b"a", b"1"), (b"c", b"3")])
        t.set(b"a", b"1.1")
        t.set(b"b", b"2")
        r2 = t.commit(2)
        t.delete(b"c")
        r3 = t.commit(3)
        assert len({r1, r2, r3}) == 3
        # point reads at each version
        assert t.get(b"a", 1) == b"1"
        assert t.get(b"a", 2) == b"1.1"
        assert t.get(b"b", 1) is None
        assert t.get(b"b", 2) == b"2"
        assert t.get(b"c", 2) == b"3"
        assert t.get(b"c", 3) is None
        assert t.get(b"a") == b"1.1"          # latest
        # materialized views agree with point reads
        assert t.pairs(1) == [(b"a", b"1"), (b"c", b"3")]
        assert t.pairs(3) == [(b"a", b"1.1"), (b"b", b"2")]
        assert t.total(1) == 2 and t.total(3) == 2
        assert t.root(1) == r1 and t.root(3) == r3

    def test_working_root_is_the_commit_root(self):
        t = _tree()
        _commit_pairs(t, 1, [(b"k", b"v")])
        t.set(b"k2", b"v2")
        wr = t.working_root(2)
        # working root is a preview: committed state unchanged
        assert t.get(b"k2") is None
        assert t.commit(2) == wr
        assert t.get(b"k2") == b"v2"

    def test_reset_working_drops_staged_writes(self):
        t = _tree()
        r1 = _commit_pairs(t, 1, [(b"k", b"v")])
        t.set(b"junk", b"x")
        t.reset_working()
        # nothing staged: version 2 commits the same state as 1
        assert t.commit(2) == r1
        assert t.get(b"junk") is None

    def test_commit_discipline(self):
        t = _tree()
        r1 = _commit_pairs(t, 1, [(b"k", b"v")])
        # identical re-commit of the latest version is a no-op
        # (InitChain replay after a crash before height 1)
        assert t.commit(1) == r1
        # conflicting re-commit is an error
        t.set(b"k", b"other")
        with pytest.raises(ValueError, match="conflicting"):
            t.commit(1)
        t.reset_working()
        # non-monotonic commit is an error
        t.set(b"x", b"y")
        with pytest.raises(ValueError, match="<= latest"):
            t.commit(0)

    def test_empty_key_rejected(self):
        with pytest.raises(ValueError):
            _tree().set(b"", b"v")

    def test_deterministic_across_instances(self):
        """Same pairs, any insertion order -> same root (sorted-kv
        commitment)."""
        pairs = [(b"k%02d" % i, b"v%d" % i) for i in range(40)]
        a = _commit_pairs(_tree(), 1, pairs)
        b = _commit_pairs(_tree(), 1, list(reversed(pairs)))
        assert a == b

    @pytest.mark.parametrize("keys,changed", [(1, 1), (7, 2), (40, 5),
                                              (40, 0)])
    def test_state_root_counts_the_digests_it_takes(
            self, monkeypatch, tmp_path, keys, changed):
        """The state_root span's ``hashes`` is what
        sync_root_hashes_per_height reads: it has to be the number of
        digests working_root really took, leaves and inner nodes, so
        that a root built another way moves it without being told."""
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.statetree import tree as tree_mod

        t = _tree()
        _commit_pairs(t, 1, [(b"k%02d" % i, b"v") for i in range(keys)])
        for i in range(changed):
            t.set(b"k%02d" % i, b"w")

        taken = []
        real_inner, real_leaf = merkle.inner_hash, merkle.leaf_hash
        monkeypatch.setattr(
            merkle, "inner_hash",
            lambda l, r: taken.append("inner") or real_inner(l, r))
        monkeypatch.setattr(
            merkle, "leaf_hash",
            lambda it: taken.append("leaf") or real_leaf(it))
        # the native batch hasher would take the leaf digests unseen
        monkeypatch.setattr(tree_mod, "batched_hashes",
                            lambda *a, **kw: None)
        old = tracing.set_recorder(
            tracing.Recorder(dump_dir=str(tmp_path)))
        try:
            t.working_root(2)
            (ev,) = tracing.snapshot()
        finally:
            tracing.set_recorder(old)
        assert ev["name"] == "state_root"
        assert set(ev["attrs"]) == {"hashes"}
        assert ev["attrs"]["hashes"] == len(taken)
        assert taken.count("leaf") == changed
        assert taken.count("inner") == keys - 1

    def test_reopen_recovers_exact_root(self, tmp_path):
        """Crash/restart: a new StateTree over the same db recovers
        the exact latest root, version, and per-version reads."""
        db = SQLiteDB(str(tmp_path / "t.db"))
        t = StateTree(db)
        _commit_pairs(t, 1, [(b"a", b"1"), (b"b", b"2")])
        t.set(b"a", b"1.1")
        t.delete(b"b")
        r2 = t.commit(2, extra={"size": 3})

        t2 = StateTree(db)
        assert t2.latest_version == 2
        assert t2.root() == r2
        assert t2.root(1) == t.root(1)
        assert t2.get(b"a") == b"1.1"
        assert t2.get(b"b", 1) == b"2" and t2.get(b"b") is None
        assert t2.version_extra() == {"size": 3}
        # proofs from the reopened tree verify against the old root
        env = t2.prove([b"a", b"b"], 2)
        verify_proof_envelope(env, present=[(b"a", b"1.1")],
                              absent=[b"b"], expected_root=r2)


# ---------------------------------------------------------------------------
# proof envelopes: existence + non-inclusion, and the tamper matrix


def _proof_tree():
    t = _tree()
    pairs = [(b"k%02d" % i, b"v%d" % i) for i in range(0, 20, 2)]
    root = _commit_pairs(t, 1, pairs)
    return t, dict(pairs), root


class TestProofEnvelope:
    def test_present_and_absent_verify(self):
        t, pairs, root = _proof_tree()
        env = t.prove([b"k04", b"k09", b"zzz", b"aaa"], 1)
        verify_proof_envelope(
            env, present=[(b"k04", pairs[b"k04"])],
            absent=[b"k09", b"zzz", b"aaa"], expected_root=root)
        assert env["header_height"] == "2"
        assert sorted(env["missing"]) == sorted(
            [b"k09".hex(), b"zzz".hex(), b"aaa".hex()])
        # envelopes are JSON-stable (the RPC wire format)
        rt = json.loads(json.dumps(env))
        verify_proof_envelope(rt, present=[(b"k04", pairs[b"k04"])],
                              absent=[b"k09"], expected_root=root)

    def test_empty_tree_absence(self):
        t = _tree()
        env = t.prove([b"anything"])
        verify_proof_envelope(env, absent=[b"anything"],
                              expected_root=merkle.empty_hash())
        # the same claim against a non-empty tree is rejected
        t2, _, root2 = _proof_tree()
        env2 = t2.prove([b"zzz"], 1)
        arm = env2["absent"][0]
        arm["left"] = arm["right"] = None
        with pytest.raises(ValueError, match="empty-tree"):
            verify_proof_envelope(env2, absent=[b"zzz"],
                                  expected_root=root2)

    def test_stale_version_proof_rejected(self):
        """A proof from version 1 — internally consistent — must not
        verify against version 2's root (the newer header's
        app_hash)."""
        t, pairs, root1 = _proof_tree()
        t.set(b"k04", b"mutated")
        root2 = t.commit(2)
        env_old = t.prove([b"k04"], 1)
        verify_proof_envelope(env_old,
                              present=[(b"k04", pairs[b"k04"])],
                              expected_root=root1)
        with pytest.raises(ValueError, match="stale version|forged"):
            verify_proof_envelope(env_old,
                                  present=[(b"k04", pairs[b"k04"])],
                                  expected_root=root2)

    def test_neighbor_swap_forgery_rejected(self):
        """Rewriting an absence arm onto a DIFFERENT adjacent proven
        pair (which does not straddle the key) must fail."""
        t, pairs, root = _proof_tree()
        # k05 is absent between k04 (idx 2) and k06 (idx 3); also
        # prove k00/k02 so the forged arm can reference proven leaves
        env = t.prove([b"k00", b"k02", b"k05"], 1)
        arm = next(a for a in env["absent"])
        assert (arm["left"], arm["right"]) == (2, 3)
        arm["left"], arm["right"] = 0, 1       # adjacent, wrong gap
        with pytest.raises(ValueError, match="neighbor-swap"):
            verify_proof_envelope(env, absent=[b"k05"],
                                  expected_root=root)

    def test_range_gap_forgery_rejected(self):
        """An arm claiming two NON-adjacent leaves as neighbors would
        hide every key committed between them."""
        t, pairs, root = _proof_tree()
        env = t.prove([b"k00", b"k05"], 1)
        arm = env["absent"][0]
        arm["left"], arm["right"] = 0, 3       # skips leaves 1,2
        with pytest.raises(ValueError, match="range-gap"):
            verify_proof_envelope(env, absent=[b"k05"],
                                  expected_root=root)

    def test_arm_referencing_unproven_leaf_rejected(self):
        t, pairs, root = _proof_tree()
        env = t.prove([b"k05"], 1)
        env["absent"][0]["left"], env["absent"][0]["right"] = 5, 6
        with pytest.raises(ValueError, match="unproven leaf"):
            verify_proof_envelope(env, absent=[b"k05"],
                                  expected_root=root)

    def test_edge_absences(self):
        t, pairs, root = _proof_tree()
        env = t.prove([b"a-first", b"zzz"], 1)
        verify_proof_envelope(env, absent=[b"a-first", b"zzz"],
                              expected_root=root)
        # left-edge arm must anchor at leaf 0
        bad = t.prove([b"a-first", b"k02"], 1)
        bad["absent"][0]["right"] = 1
        with pytest.raises(ValueError, match="left-edge"):
            verify_proof_envelope(bad, absent=[b"a-first"],
                                  expected_root=root)
        # right-edge arm must anchor at the last leaf
        bad2 = t.prove([b"zzz", b"k16"], 1)
        bad2["absent"][0]["left"] = 8
        with pytest.raises(ValueError, match="right-edge"):
            verify_proof_envelope(bad2, absent=[b"zzz"],
                                  expected_root=root)

    def test_value_and_root_tamper_rejected(self):
        t, pairs, root = _proof_tree()
        env = t.prove([b"k04"], 1)
        forged = json.loads(json.dumps(env))
        forged["values"][0] = b"forged".hex()
        with pytest.raises(ValueError):
            verify_proof_envelope(forged,
                                  present=[(b"k04", b"forged")],
                                  expected_root=root)
        forged2 = json.loads(json.dumps(env))
        forged2["root"] = "00" * 32
        with pytest.raises(ValueError):
            verify_proof_envelope(forged2,
                                  present=[(b"k04", pairs[b"k04"])],
                                  expected_root=root)

    def test_claims_must_be_covered(self):
        t, pairs, root = _proof_tree()
        env = t.prove([b"k04"], 1)
        with pytest.raises(ValueError, match="not covered"):
            verify_proof_envelope(env, present=[(b"k06", b"v6")],
                                  expected_root=root)
        with pytest.raises(ValueError, match="no non-inclusion arm"):
            verify_proof_envelope(env, absent=[b"k05"],
                                  expected_root=root)
        with pytest.raises(ValueError, match="value mismatch"):
            verify_proof_envelope(env, present=[(b"k04", b"wrong")],
                                  expected_root=root)
        # a key proven present cannot be claimed absent
        env2 = t.prove([b"k04", b"k05"], 1)
        with pytest.raises(ValueError, match="claimed absent"):
            verify_proof_envelope(env2, absent=[b"k04"],
                                  expected_root=root)

    def test_unsorted_leaves_rejected(self):
        """A forged envelope whose proven keys are out of order cannot
        make adjacency claims."""
        keys = [b"a", b"b"]
        values = [b"1", b"2"]
        # swap the leaves but keep a consistent multiproof over them
        leaves = [merkle.value_op_leaf(k, v)
                  for k, v in zip(keys, values)]
        hashes = [merkle.leaf_hash(item) for item in leaves]
        env = build_proof_envelope(
            [b"a", b"b"], keys, values, hashes,
            {b"a": 0, b"b": 1}, 1)
        env["keys"] = [b"b".hex(), b"a".hex()]
        env["values"] = [b"2".hex(), b"1".hex()]
        with pytest.raises(ValueError):
            verify_proof_envelope(
                env, present=[(b"a", b"1")],
                expected_root=bytes.fromhex(env["root"]))


# ---------------------------------------------------------------------------
# pruning: retention + cache pins


class TestPruning:
    def _tree_5_versions(self):
        t = _tree()
        for v in range(1, 6):
            t.set(b"hot", b"v%d" % v)
            t.set(b"k%d" % v, b"x")
            t.commit(v)
        return t

    def test_prune_keeps_retained_and_pinned(self):
        t = self._tree_5_versions()
        roots = {v: t.root(v) for v in range(1, 6)}
        pins = {2}
        dropped = t.prune(4, pinned=pins)
        assert dropped == 2                       # versions 1 and 3
        assert t.base_version == 2
        assert sorted(t.versions()) == [2, 4, 5]
        # retained + pinned versions materialize the exact same state
        assert t.get(b"hot", 2) == b"v2"
        assert t.get(b"hot", 4) == b"v4"
        assert t.pairs(2) == [(b"hot", b"v2"), (b"k1", b"x"),
                              (b"k2", b"x")]
        # ... and still prove against their original roots: pruning
        # never breaks a cached-height proof (the ISSUE invariant)
        for v in (2, 4, 5):
            env = t.prove([b"hot", b"absent"], v)
            verify_proof_envelope(env, present=[(b"hot", b"v%d" % v)],
                                  absent=[b"absent"],
                                  expected_root=roots[v])
        # dropped versions are gone
        with pytest.raises(KeyError):
            t.prove([b"hot"], 3)
        assert t.get(b"hot", 1) is None

    def test_prune_survives_reopen(self, tmp_path):
        db = SQLiteDB(str(tmp_path / "t.db"))
        t = StateTree(db)
        for v in range(1, 4):
            t.set(b"k", b"v%d" % v)
            t.commit(v)
        r3 = t.root(3)
        t.prune(3)
        t2 = StateTree(db)
        assert t2.base_version == 3 and t2.root() == r3
        assert t2.get(b"k") == b"v3"

    def test_prune_everything_below_tip(self):
        t = self._tree_5_versions()
        r5 = t.root(5)
        assert t.prune(10) == 4                   # clamped to latest
        assert t.versions() == [5] and t.root() == r5
        env = t.prove([b"hot"], 5)
        verify_proof_envelope(env, present=[(b"hot", b"v5")],
                              expected_root=r5)

    def test_kvstore_retain_blocks_pins_cached_heights(self):
        """The app prunes on retain_blocks but must keep any version
        the lightserve ResponseCache still serves (node.py wires
        version_pin = cache.heights)."""
        from cometbft_tpu.lightserve.cache import ResponseCache
        app = KVStoreApplication()
        app.retain_blocks = 2
        cache = ResponseCache(max_bytes=1 << 20)
        app.version_pin = cache.heights

        async def go():
            await _drive_blocks(app, [[b"a=1"]])
            root1 = app.tree.root(1)
            cache.put("abci_query_batch", 1, (), {"cached": True},
                      latest_height=99)
            await _drive_blocks(
                app, [[b"b=2"], [b"c=3"], [b"d=4"], [b"e=5"]],
                start_height=2)
            # at height 5 the horizon is retain_height=4; the app
            # keeps version 3 (the replay base) and up, plus pins
            assert sorted(app.tree.versions()) == [1, 3, 4, 5]
            # version 1 outlived the horizon only via the cache pin —
            # and is still fully provable
            env = app.tree.prove([b"a", b"zz"], 1)
            verify_proof_envelope(env, present=[(b"a", b"1")],
                                  absent=[b"zz"], expected_root=root1)
        run(go())


# ---------------------------------------------------------------------------
# kvstore integration: versioned queries, restart, statesync restore


class TestKVStoreStateTree:
    def test_historical_queries(self):
        app = KVStoreApplication()

        async def go():
            await _drive_blocks(app, [[b"a=1"], [b"a=2", b"b=9"]])
            q1 = await app.query(abci.QueryRequest(data=b"a",
                                                   height=1))
            assert q1.value == b"1" and q1.height == 1
            q2 = await app.query(abci.QueryRequest(data=b"a"))
            assert q2.value == b"2"
            qb = await app.query(abci.QueryRequest(data=b"b",
                                                   height=1))
            assert qb.log == "does not exist"
            # unservable heights answer with a coded error, not junk
            for h in (7, -3):
                qe = await app.query(abci.QueryRequest(data=b"a",
                                                       height=h))
                assert qe.code != 0 and qe.log
        run(go())

    def test_multistore_envelope_historical(self):
        app = KVStoreApplication()

        async def go():
            await _drive_blocks(app, [[b"a=1"], [b"a=2"]])
            req = json.dumps(
                {"keys": [b"a".hex(), b"gone".hex()]}).encode()
            res = await app.query(abci.QueryRequest(
                path="/multistore", data=req, height=1))
            assert res.code == 0
            env = json.loads(res.value)
            assert env["version"] == "1" and res.height == 1
            verify_proof_envelope(env, present=[(b"a", b"1")],
                                  absent=[b"gone"],
                                  expected_root=app.tree.root(1))
            bad = await app.query(abci.QueryRequest(
                path="/multistore", data=b"not json", height=0))
            assert bad.code != 0
        run(go())

    def test_restart_recovers_root_and_size(self, tmp_path):
        db = SQLiteDB(str(tmp_path / "kv.db"))
        app = KVStoreApplication(db=db)

        async def go():
            await _drive_blocks(app, [[b"k=v"], [b"k2=v2"]])
        run(go())
        expected = app.tree.root(2)
        app2 = KVStoreApplication(db=db)

        async def go2():
            info = await app2.info(abci.InfoRequest())
            assert info.last_block_height == 2
            assert info.last_block_app_hash == expected
            assert json.loads(info.data)["size"] == 2
            # historical state survives the restart
            q = await app2.query(abci.QueryRequest(data=b"k2",
                                                   height=1))
            assert q.log == "does not exist"
        run(go2())

    def test_statesync_restore_reproduces_identical_root(self):
        """The acceptance test for snapshot restore: the consumer's
        tree root is byte-identical to the producer's, so the restored
        node reports the same app_hash and serves verifying proofs."""
        producer = KVStoreApplication(snapshot_interval=2)

        async def go():
            await _drive_blocks(
                producer, [[b"a=1", b"b=2"], [b"c=3", b"a=9"]])
            snaps = await producer.list_snapshots(
                abci.ListSnapshotsRequest())
            assert [s.height for s in snaps.snapshots] == [2]
            snap = snaps.snapshots[0]

            consumer = KVStoreApplication()
            offer = await consumer.offer_snapshot(
                abci.OfferSnapshotRequest(snapshot=snap))
            assert offer.result == \
                abci.OFFER_SNAPSHOT_RESULT_ACCEPT
            chunk = await producer.load_snapshot_chunk(
                abci.LoadSnapshotChunkRequest(height=2, format=1,
                                              chunk=0))
            applied = await consumer.apply_snapshot_chunk(
                abci.ApplySnapshotChunkRequest(index=0,
                                               chunk=chunk.chunk))
            assert applied.result == \
                abci.APPLY_SNAPSHOT_CHUNK_RESULT_ACCEPT

            assert consumer.tree.root(2) == producer.tree.root(2)
            info = await consumer.info(abci.InfoRequest())
            assert info.last_block_height == 2
            assert info.last_block_app_hash == producer.tree.root(2)
            env = consumer.tree.prove([b"a", b"zz"], 2)
            verify_proof_envelope(env, present=[(b"a", b"9")],
                                  absent=[b"zz"],
                                  expected_root=producer.tree.root(2))
            # a corrupted chunk is rejected, state untouched
            bad = await consumer.apply_snapshot_chunk(
                abci.ApplySnapshotChunkRequest(index=0,
                                               chunk=b"garbage"))
            assert bad.result == \
                abci.APPLY_SNAPSHOT_CHUNK_RESULT_REJECT_SNAPSHOT
        run(go())

    def test_legacy_store_migration(self):
        """A pre-tree db (raw kvPairKey: rows + appstate JSON) imports
        into the tree at its height under the LEGACY app hash, so
        handshake replay of the already-finalized height still
        matches; the next height reports the tree root."""
        db = MemDB()
        db.set(b"kvPairKey:old", b"value")
        db.set(b"appstate",
               json.dumps({"height": 3, "size": 4}).encode())
        app = KVStoreApplication(db=db)
        assert app._height == 3 and app._size == 4
        assert app._app_hash() == _zigzag_varint(4)
        assert app.tree.get(b"old") == b"value"
        assert db.get(b"kvPairKey:old") is None    # legacy rows gone

        async def go():
            r = await _drive_blocks(app, [[b"new=1"]],
                                    start_height=4)
            # after the migrated height the app reports tree roots
            assert r[0].app_hash == app.tree.root(4)
            assert len(app._app_hash()) == 32
            assert app._app_hash() == app.tree.root(4)
            q = await app.query(abci.QueryRequest(data=b"old"))
            assert q.value == b"value"
        run(go())
