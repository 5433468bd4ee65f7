"""Framework substrates: checkpointing, data pipeline, curation, serving."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.checkpoint import CheckpointStore, latest_step
from repro.data.curation import StreamCurator
from repro.data.pipeline import TokenPipeline
from repro.data.synthetic import dataset, gaussian_mixtures, sliding_window_workload
from repro.models import model as M
from repro.serving.engine import Request, ServeEngine
from conftest import make_blobs


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4, jnp.bfloat16)}}
        store = CheckpointStore(str(tmp_path), keep=2)
        store.save(10, tree)
        step, out = store.restore(like=tree)
        store.close()
        assert step == 10
        np.testing.assert_array_equal(out["a"], tree["a"])
        assert out["b"]["c"].dtype == jnp.bfloat16

    def test_async_save_and_retention(self, tmp_path):
        tree = {"w": jnp.zeros((8, 8))}
        store = CheckpointStore(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            store.save(s, jax.tree.map(lambda x, s=s: x + s, tree), blocking=False)
        store.wait()
        assert latest_step(str(tmp_path)) == 4
        kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
        assert kept == ["step_3", "step_4"]  # retention
        step, out = store.restore(like=tree)
        store.close()
        assert float(out["w"][0, 0]) == 4.0

    def test_corruption_detected(self, tmp_path):
        tree = {"w": jnp.ones((4,))}
        store = CheckpointStore(str(tmp_path), keep=2)
        store.save(1, tree)
        # corrupt a payload file
        d = tmp_path / "step_1"
        leaf = next(f for f in os.listdir(d) if f.endswith(".npy"))
        arr = np.load(d / leaf)
        np.save(d / leaf, arr + 99)
        with pytest.raises(IOError):
            store.restore(like=tree)
        store.close()

    def test_elastic_dtype_cast(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        store.save(1, {"w": jnp.ones((4,), jnp.float32)})
        _, out = store.restore(like={"w": jnp.zeros((4,), jnp.bfloat16)})
        store.close()
        assert out["w"].dtype == jnp.bfloat16

    def test_crash_window_never_loses_published_step(self, tmp_path, monkeypatch):
        """Regression: overwriting a step used to rmtree the old copy
        BEFORE publishing the new one — a crash in the gap lost the only
        copy.  Now the old dir is renamed aside first; a crash between
        the two renames rolls back to the old copy at next store open."""
        tree_old = {"w": jnp.arange(4.0)}
        store = CheckpointStore(str(tmp_path), keep=2)
        store.save(7, tree_old)

        real_rename = os.rename

        def crash_on_publish(src, dst):
            if ".tmp-" in os.path.basename(src):  # the publish rename
                raise OSError("simulated crash between rename-aside and publish")
            return real_rename(src, dst)

        monkeypatch.setattr(os, "rename", crash_on_publish)
        with pytest.raises(OSError, match="simulated crash"):
            store.save(7, {"w": jnp.arange(4.0) + 100})
        monkeypatch.undo()
        # at no point did the step's data leave disk: the old copy
        # survives as step_7.old-<pid> and a fresh store restores it
        store2 = CheckpointStore(str(tmp_path), keep=2)
        assert latest_step(str(tmp_path)) == 7
        step, out = store2.restore(like=tree_old)
        assert step == 7
        np.testing.assert_array_equal(out["w"], tree_old["w"])
        # the next successful write GCs the stale tmp dir
        store2.save(8, tree_old)
        leftovers = [d for d in os.listdir(tmp_path) if ".tmp-" in d or ".old-" in d]
        assert leftovers == []
        store2.close()
        store._err = None  # crash already surfaced above; close cleanly
        store.close()

    def test_async_error_latched_first_wins(self, tmp_path):
        """A failed async write must surface on the NEXT save()/wait(),
        and a second failure must not mask the first exception."""
        tree = {"w": jnp.ones((2,))}
        store = CheckpointStore(str(tmp_path), keep=2)

        def failing_write(step, host):
            raise ValueError(f"disk full at step {step}")

        store._write = failing_write
        store.save(1, tree, blocking=False)
        store._q.join()  # writer has latched boom-1
        host = {"w": np.ones((2,))}
        store._q.put((2, host))  # bypass save(): force a SECOND failure
        store._q.join()
        with pytest.raises(RuntimeError, match="checkpoint writer failed") as ei:
            store.save(3, tree, blocking=False)
        assert "step 1" in str(ei.value.__cause__)  # first failure preserved
        with pytest.raises(RuntimeError):
            store.wait()
        with pytest.raises(RuntimeError):
            store.close()

    def test_stale_writer_dirs_ignored_and_gcd(self, tmp_path):
        """step_N.tmp-<pid> / step_N.old-<pid> left by a killed writer
        are invisible to latest_step and swept by the next GC."""
        tree = {"w": jnp.ones((2,))}
        store = CheckpointStore(str(tmp_path), keep=3)
        store.save(3, tree)
        store.save(4, tree)
        for stale in ("step_9.tmp-12345", "step_3.old-12345"):
            d = tmp_path / stale
            d.mkdir()
            (d / "leaf_00000.npy").write_bytes(b"junk")
        assert latest_step(str(tmp_path)) == 4  # stale dirs never surfaced
        store.save(5, tree)  # triggers _gc
        names = sorted(os.listdir(tmp_path))
        assert names == ["step_3", "step_4", "step_5"]
        store.close()

    def test_truncated_leaf_raises_checksum_error(self, tmp_path):
        tree = {"w": jnp.arange(16.0)}
        store = CheckpointStore(str(tmp_path), keep=2)
        store.save(1, tree)
        d = tmp_path / "step_1"
        leaf = next(f for f in os.listdir(d) if f.endswith(".npy"))
        arr = np.load(d / leaf)
        np.save(d / leaf, arr[:-3])  # truncated payload, valid npy header
        with pytest.raises(IOError, match="checksum mismatch"):
            store.restore(like=tree)
        store.close()


class TestPipeline:
    def test_deterministic_replay(self):
        a = TokenPipeline(100, 4, 16, seed=7)
        next(a)
        b2 = next(a)
        a.close()
        # restart from step 1: identical second batch (restart guarantee)
        b = TokenPipeline(100, 4, 16, seed=7, start_step=1)
        r2 = next(b)
        b.close()
        np.testing.assert_array_equal(b2["tokens"], r2["tokens"])

    def test_host_sharding_disjoint_shapes(self):
        p0 = TokenPipeline(100, 8, 16, seed=1, host_id=0, n_hosts=2)
        p1 = TokenPipeline(100, 8, 16, seed=1, host_id=1, n_hosts=2)
        a, b = next(p0), next(p1)
        p0.close(), p1.close()
        assert a["tokens"].shape == (4, 16)
        assert not np.array_equal(a["tokens"], b["tokens"])  # different shards

    def test_labels_are_shifted_tokens(self):
        p = TokenPipeline(50, 2, 8, seed=3)
        b = p.batch_at(0)
        p.close()
        assert b["tokens"].shape == (2, 8) and b["labels"].shape == (2, 8)


class TestSynthetic:
    def test_gaussian_mixtures_structure(self):
        X, y = gaussian_mixtures(2000, d=10, k=5, overlap=0.1, seed=1)
        assert X.shape == (2000, 10)
        assert len(set(y.tolist())) == 5
        # clusters separated: static HDBSCAN should find most of them
        from repro.core import hdbscan, nmi

        res = hdbscan(X[:800], min_pts=10)
        m = res.labels >= 0
        assert nmi(res.labels[m], y[:800][m]) > 0.8

    def test_dataset_specs(self):
        X, y = dataset("intrusion", 500, seed=0)
        assert X.shape == (500, 34)
        assert (y == -1).any()  # noise floor

    def test_sliding_window_workload(self):
        X = np.arange(100, dtype=np.float64).reshape(50, 2)
        slides = list(sliding_window_workload(X, window=20, slide=10))
        assert slides[0][1] == 0 and slides[0][0].shape == (20, 2)
        assert all(s[1] == 10 for s in slides[1:])
        total = sum(s[0].shape[0] for s in slides)
        assert total == 50


class TestCuration:
    def test_observe_retire_curate(self, rng):
        X, y = make_blobs(rng, n_per=80)
        cur = StreamCurator(dim=2, min_pts=8, compression=0.12)
        cur.observe_block(range(240), X)
        rep = cur.curate(step=1)
        assert rep.n_clusters == 3
        assert rep.n_examples == 240
        # retire blob 0 entirely -> cluster count drops, drift fires
        for i in np.nonzero(y == 0)[0]:
            cur.retire(int(i))
        rep2 = cur.curate(step=2)
        assert rep2.n_clusters == 2
        assert rep2.n_examples == 160

    def test_sampling_weights_balance(self, rng):
        # imbalanced blobs: 300 vs 30 points
        big = rng.normal(size=(300, 2))
        small = rng.normal(loc=8.0, size=(30, 2))
        cur = StreamCurator(dim=2, min_pts=8, compression=0.15)
        cur.observe_block(range(330), np.concatenate([big, small]))
        w = cur.sampling_weights(np.array([[0.0, 0.0], [8.0, 8.0]]))
        assert w[1] > w[0]  # rare cluster upweighted
        assert w.sum() == pytest.approx(1.0)


class TestServing:
    @pytest.fixture(scope="class")
    def engine_setup(self):
        cfg = C.get_smoke("qwen1.5-0.5b")
        values, _ = M.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, values

    def test_continuous_batching_completes(self, engine_setup):
        cfg, values = engine_setup
        eng = ServeEngine(cfg, values, slots=3, cache_len=64)
        rng = np.random.default_rng(0)
        reqs = [
            Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 10))).astype(np.int32), max_new_tokens=6)
            for i in range(7)
        ]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        assert all(len(r.generated) == 6 for r in reqs)
        # more requests than slots => continuous batching actually cycled
        assert eng.steps >= 6

    def test_greedy_decode_matches_model(self, engine_setup):
        """Engine greedy output == teacher-forced full-prefill oracle
        (prefill(seq)'s last-position logits are the exact next-token
        distribution — no cache-size pitfalls)."""
        cfg, values = engine_setup
        model = M.build_model(cfg)
        prompt = np.arange(5, dtype=np.int32) + 3
        eng = ServeEngine(cfg, values, slots=2, cache_len=64)
        req = Request(rid=0, prompt=prompt, max_new_tokens=4)
        eng.submit(req)
        eng.run()
        pf = jax.jit(model.prefill)
        seq = list(prompt)
        toks = []
        for _ in range(4):
            lg, _ = pf(values, jnp.asarray(seq, jnp.int32)[None])
            t = int(np.argmax(np.asarray(lg[0, -1].astype(jnp.float32))[: cfg.vocab_size]))
            toks.append(t)
            seq.append(t)
        assert req.generated == toks

    def test_eos_terminates(self, engine_setup):
        cfg, values = engine_setup
        eng = ServeEngine(cfg, values, slots=1, cache_len=64)
        req = Request(rid=0, prompt=np.array([1, 2, 3], np.int32), max_new_tokens=50, eos_id=None)
        # force eos = whatever greedy emits first
        eng.submit(req)
        eng.step()
        first = req.generated[0]
        eng2 = ServeEngine(cfg, values, slots=1, cache_len=64)
        req2 = Request(rid=0, prompt=np.array([1, 2, 3], np.int32), max_new_tokens=50, eos_id=first)
        eng2.submit(req2)
        eng2.run()
        assert req2.done and req2.generated[-1] == first and len(req2.generated) <= 2
