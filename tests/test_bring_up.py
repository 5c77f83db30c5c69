"""No fallback hides the device: the CPU checks of the repairs made for the
first run on the chip (CHANGES.md, PR 21).  Each of these failed, or could
not be written, at the parent commit."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals import parse_graph as pg


# -- kernels: a selected kernel that fails raises ------------------------------

def test_selected_knn_kernel_failure_propagates(monkeypatch):
    from pathway_tpu.ops import knn_pallas

    def boom(*a, **k):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(knn_pallas, "pallas_scores", boom)
    m = np.eye(8, 4, dtype=np.float32)
    q = np.ones((2, 4), np.float32)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        knn_pallas.knn_topk(m, q, 2, use_pallas=True)
    # not selected: the jnp matmul, untouched by the kernel's failure
    vals, idx = knn_pallas.knn_topk(m, q, 2, use_pallas=False)
    assert vals.shape == idx.shape == (2, 2)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def test_pallas_engine_at_head_dim_64_runs_kernel_on_the_pool_in_place():
    """hd = 64 (the whole GPT-2 family): attn="pallas" stays "pallas", the
    step programs hold the kernel, and nothing pool-sized is padded."""
    from pathway_tpu.kvcache.engine import PagedDecodeEngine
    from pathway_tpu.models.decoder import (DecoderConfig, init_decoder_params,
                                            paged_decode_step,
                                            paged_mixed_step,
                                            plan_decode_params)

    cfg = DecoderConfig(vocab_size=128, d_model=128, n_layers=2, n_heads=2,
                        d_ff=128, max_len=64, dtype=jnp.float32)
    params = init_decoder_params(cfg, jax.random.PRNGKey(0))
    kw = dict(num_blocks=16, block_size=8, max_batch_size=2, chain_steps=4)
    eng = PagedDecodeEngine(cfg, params, attn="pallas", name="t_hd64", **kw)
    assert eng.attn == "pallas"
    # heads fused on the minor axis, at the pool's own head_dim
    assert eng.pool.k.shape[-1] == cfg.n_heads * 64
    ref = PagedDecodeEngine(cfg, params, attn="reference", name="t_hd64_ref",
                            **kw)
    reqs = [([3, 4, 5, 6, 7], 6), ([9, 8, 7], 6)]
    assert eng.generate_batch(reqs) == ref.generate_batch(reqs)

    plan = plan_decode_params(cfg, params)
    pool = jnp.zeros(eng.pool.k.shape, jnp.float32)
    layer_slice = pool[0].size
    Bn, C, NBs = 2, eng.prefill_chunk, eng.max_blocks_per_seq
    T = Bn + C
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    jaxprs = {
        "decode": jax.make_jaxpr(
            lambda p, k, v: paged_decode_step(
                p, cfg, k, v, i32(Bn), i32(Bn), i32(Bn, NBs), i32(Bn),
                i32(Bn), attn="pallas"))(plan, pool, pool),
        "mixed": jax.make_jaxpr(
            lambda p, k, v: paged_mixed_step(
                p, cfg, k, v, i32(T), i32(T), i32(Bn, NBs), i32(Bn),
                jnp.ones((Bn,), jnp.int32), i32(Bn, C), i32(T), i32(T),
                i32(T), i32(T), i32(Bn), attn="pallas"))(plan, pool, pool),
    }
    for name, jp in jaxprs.items():
        prims = [e for e in _walk_eqns(jp.jaxpr)]
        assert any(e.primitive.name == "pallas_call" for e in prims), name
        for e in prims:
            if e.primitive.name == "pad":
                assert e.invars[0].aval.size < layer_slice, (
                    f"{name} step pads a pool-sized operand: "
                    f"{e.invars[0].aval}"
                )
            # the kernels take the stacked pool and the layer index: no
            # layer is sliced out of the pool or written back into it
            for v in e.outvars:
                assert v.aval.size != layer_slice, (
                    f"{name} step materialises one layer of the pool: "
                    f"{e.primitive.name} -> {v.aval}"
                )


# -- engines: cannot be built on a TPU backend is an error ---------------------

class _Unbuildable:
    def __init__(self, cfg, params, **kw):
        raise ValueError("KV pool does not fit HBM")


def test_build_engine_reraises_on_tpu_backend(monkeypatch):
    from pathway_tpu.kvcache import engine

    # the CPU's serial tier: logged, None
    assert engine.build_engine(None, None, "serial path", __name__,
                               engine_cls=_Unbuildable) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU backend") as exc:
        engine.build_engine(None, None, "serial path", __name__,
                            engine_cls=_Unbuildable)
    assert isinstance(exc.value.__cause__, ValueError)


def test_hbm_budget_is_never_unenforced_on_tpu(monkeypatch):
    from pathway_tpu.obs import memory

    monkeypatch.delenv("PW_HBM_BUDGET_BYTES", raising=False)
    assert memory.resolve_budget() == (None, "none")  # CPU: no budget known

    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [_Dev({"bytes_limit": 123})])
    assert memory.resolve_budget() == (123, "device:memory_stats")
    monkeypatch.setattr(jax, "devices", lambda: [_Dev({})])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.resolve_budget()


def test_tpu_peak_comes_from_the_table_or_is_null(monkeypatch):
    from pathway_tpu.obs import profiler

    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(profiler, "_PROBE_CACHE", {})
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v5 lite")])
    assert profiler.measured_peak_flops() == 197e12
    assert profiler.measured_membw() == 819e9
    monkeypatch.setattr(profiler, "_PROBE_CACHE", {})
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v9 imaginary")])
    assert profiler.measured_peak_flops() is None
    summary = profiler.registry().summary()
    assert summary["peak_flops_per_s"] is None
    assert "TPU v9 imaginary" in summary["peak_source"]
    assert all("mfu" not in row for row in summary["programs"])


# -- the dataflow's device tier: a failure is counted, not swallowed ----------

def test_jax_tier_build_failure_is_counted_and_cached(monkeypatch):
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine import vectorize
    from pathway_tpu.engine.runner import run_tables

    calls = {"n": 0}

    def broken_build(exprs, positions):
        calls["n"] += 1
        raise ImportError("cannot import name 'enable_x64'")

    monkeypatch.setattr(vectorize, "_build_jax", broken_build)
    monkeypatch.setattr(vectorize, "JAX_THRESHOLD", 64)
    for key in vectorize.STATS:
        monkeypatch.setitem(vectorize.STATS, key, 0)

    class S(pw.Schema):
        a: int
        b: int

    pg.G.clear()
    rows = [(i, i % 7, t, 1) for t in (0, 2) for i in range(500)]
    t = table_from_rows(S, rows, is_stream=True)
    [cap] = run_tables(t.select(c=t.a * 2 + t.b))
    assert sorted(v[0] for v in cap.squash().values()) == sorted(
        2 * [i * 2 + i % 7 for i in range(500)]
    )
    assert vectorize.STATS["jax_failures"] == 1
    assert calls["n"] == 1  # the refusal is cached, not retried per batch
    assert vectorize.STATS["np_batches"] >= 2
    assert vectorize.STATS["row_batches"] == 0
    pg.G.clear()


# -- one process for each chip ---------------------------------------------------

def test_spawn_gives_the_platform_to_process_zero_only(tmp_path, monkeypatch):
    from pathway_tpu.cli import _spawn_once

    monkeypatch.setenv("JAX_PLATFORMS", "the-parents-platform")
    script = (
        "import json, os; "
        f"open(os.path.join({str(tmp_path)!r}, "
        "os.environ['PATHWAY_PROCESS_ID'] + '.json'), 'w').write("
        "json.dumps(os.environ.get('JAX_PLATFORMS')))"
    )
    assert _spawn_once([sys.executable, "-S", "-c", script], threads=1,
                       processes=3, first_port=19000) == 0
    seen = {int(p.stem): json.loads(p.read_text())
            for p in tmp_path.glob("*.json")}
    assert seen == {0: "the-parents-platform", 1: "cpu", 2: "cpu"}


# -- compile cache and generated files ------------------------------------------

def test_compile_cache_helper_picks_its_directory(monkeypatch):
    from pathway_tpu import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    before_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        # JAX reads the variable itself: no directory is set in code
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(repo, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before_min)


def test_native_library_belongs_to_the_machine_that_built_it(monkeypatch):
    from pathway_tpu import native

    lib = native.get_lib()
    if lib is None:
        pytest.skip("no native toolchain")
    here = native._so_path()
    assert lib._name == here
    # another CPU, another file: a tree copied from elsewhere is not adopted
    monkeypatch.setattr(native, "_cpu_identity", lambda: "some other cpu")
    assert native._so_path() != here


# -- the live-RAG wiring ------------------------------------------------------------

def test_document_store_ingest_keeps_vectors_on_the_device(tmp_path):
    """DocumentStore -> index: the embedder's batch_fn runs once per
    micro-batch and the index holds device handles, not host rows."""
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    for i in range(12):
        (tmp_path / f"d{i}.txt").write_text(f"alpha beta {i} gamma")
    emb = SentenceTransformerEmbedder(
        config=EncoderConfig(vocab_size=256, d_model=32, n_layers=1,
                             n_heads=4, d_ff=64, max_len=16),
        device_resident=True,
    )
    calls = {"rows": 0, "batches": []}
    one, many = emb._embed, emb._embed_many

    def count_one(text):
        calls["rows"] += 1
        return one(text)

    def count_many(texts):
        calls["batches"].append(len(texts))
        return many(texts)

    emb._embed, emb._embed_many = count_one, count_many
    docs = pw.io.fs.read(str(tmp_path), format="binary", mode="static",
                         with_metadata=True)
    store = DocumentStore(docs, retriever_factory=BruteForceKnnFactory(
        dimensions=32, embedder=emb))
    built = []
    make = store.index.index_factory
    store.index.index_factory = lambda: built.append(make()) or built[-1]

    class Q(pw.Schema):
        query: str
        k: int

    queries = table_from_rows(
        Q, [(f"beta {i}", 2, 4, 1) for i in range(5)], is_stream=True)
    got = []
    pw.io.subscribe(store.retrieve_query(queries),
                    on_change=lambda key, row, time, is_addition:
                    got.append(row["result"].value))
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    [index] = built
    assert index.n == 12 and len(index._dev_refs) == 12
    assert calls["rows"] == 0 and sorted(calls["batches"]) == [5, 12]
    assert len(got) == 5 and all(len(r) == 2 for r in got)


def test_adaptive_rag_answers_through_the_llm_scheduler(tmp_path):
    """llm_scheduler=True on the adaptive answerer used to be ignored:
    answers went to llm(...) one by one, past generate_batch."""
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.question_answering import (
        AdaptiveRAGQuestionAnswerer,
    )

    (tmp_path / "a.txt").write_text("the answer is forty two")

    class BatchOnlyLLM:
        batches: list = []

        def __call__(self, messages):
            raise AssertionError("the serial entry point was called")

        def generate_batch(self, message_batches, **kw):
            self.batches.append(len(message_batches))
            return ["forty two"] * len(message_batches)

    emb = SentenceTransformerEmbedder(
        config=EncoderConfig(vocab_size=256, d_model=32, n_layers=1,
                             n_heads=4, d_ff=64, max_len=16))
    docs = pw.io.fs.read(str(tmp_path), format="binary", mode="static",
                         with_metadata=True)
    store = DocumentStore(docs, retriever_factory=BruteForceKnnFactory(
        dimensions=32, embedder=emb))
    llm = BatchOnlyLLM()
    rag = AdaptiveRAGQuestionAnswerer(llm, store, llm_scheduler=True)
    # the scheduler's stats block is shared by name ("llm") within a process,
    # so an earlier test of the same worker may have counted into it
    done0 = rag._llm_scheduler.stats.completed

    class P(pw.Schema):
        prompt: str

    prompts = table_from_rows(P, [("what is the answer", 4, 1)],
                              is_stream=True)
    got = []
    pw.io.subscribe(rag.answer_query(prompts),
                    on_change=lambda key, row, time, is_addition:
                    got.append(row["result"]))
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    rag._llm_scheduler.shutdown()
    assert got == ["forty two"]
    assert llm.batches == [1]
    assert rag._llm_scheduler.stats.completed - done0 == 1


def test_jax_tier_takes_integer_plans_only_where_float64_is_emulated(
        monkeypatch):
    """On a TPU float64 is emulated and differs from numpy in the last
    bits, so a float plan there would break the tier's byte-identity
    contract: it stays on the numpy tier; integer plans go to the device."""
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine import vectorize
    from pathway_tpu.engine.runner import run_tables

    monkeypatch.setenv("PW_FORCE_JAX_TIER", "1")
    monkeypatch.setattr(vectorize, "_JAX_TIER_ON", None)
    monkeypatch.setattr(vectorize, "_F64_IS_IEEE", False)
    monkeypatch.setattr(vectorize, "JAX_THRESHOLD", 64)
    for key in vectorize.STATS:
        monkeypatch.setitem(vectorize.STATS, key, 0)
    built = []
    build = vectorize._build_jax

    def spy(exprs, positions):
        built.append(len(exprs))
        return build(exprs, positions)

    monkeypatch.setattr(vectorize, "_build_jax", spy)

    class S(pw.Schema):
        a: int
        f: float

    rows = [(i, i * 0.37) for i in range(400)]
    pg.G.clear()
    t = table_from_rows(S, rows)
    [cap] = run_tables(t.select(y=t.f * 0.1 + t.a, z=t.a * 3 + 1,
                                w=pw.cast(float, t.a) * 2))
    got = sorted(cap.squash().values(), key=lambda r: r[1])
    assert got == [(f * 0.1 + a, a * 3 + 1, float(a) * 2) for a, f in rows]
    assert built == [1]  # only z was traced for the device
    assert vectorize.STATS["jax_batches"] == 1
    assert vectorize.STATS["jax_failures"] == 0
    monkeypatch.setattr(vectorize, "_JAX_TIER_ON", None)
    pg.G.clear()


def test_a_dropped_scheduler_releases_its_engine():
    """The stats registry outlives every scheduler; it must not keep them
    (and the engine, pools and weights behind their batch_fn) alive — on
    the chip a second engine then found no room for its programs."""
    import gc
    import weakref

    from pathway_tpu.serve.scheduler import RequestScheduler

    class Engine:
        def serve(self, reqs):
            return [r * 2 for r in reqs]

    engine = Engine()
    sched = RequestScheduler(engine.serve, name="t_release", max_batch_size=4)
    assert sched.submit(21) == 42
    sched.shutdown()
    gone = weakref.ref(engine)
    stats = sched.stats
    del sched, engine
    gc.collect()
    assert gone() is None
    assert stats.snapshot()["queue_depth"] == 0
