"""System ``live_rag``: Pathway's live-RAG template on the device.

``pw.io.fs.read(mode="streaming")`` -> ``DocumentStore`` +
``BruteForceKnnFactory`` + ``SentenceTransformerEmbedder`` ->
``AdaptiveRAGQuestionAnswerer(JaxChat, llm_scheduler=True)`` behind its
REST server, ``pw.run`` in a thread (the wiring of ``chip_smoke.py``'s
``phase_rag``).  The generator drives the watched directory and the REST
routes from a process of its own.

The benchmark makes the weights and the tokenizers and hands them to the
program's models and to the plain references alike.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import shutil
import socket
import tempfile
import threading
import time
import types

import numpy as np

from benchmark import compiles, tokenizer, weights
from benchmark.rest import post
from benchmark.generators import corpus
from benchmark.systems import serve_engine

LLM_COUNTERS = ("ttft_count", "mixed_steps", "chain_count", "chain_slots",
                "chain_emitted", "engine_restarts", "engine_degraded")


class LiveRag:
    @staticmethod
    def note(record: str, **fields) -> None:
        print(json.dumps({"record": record, **fields}, default=str),
              flush=True)

    def __init__(self, config: dict, seed: int, rehearse: bool):
        t_0 = time.perf_counter()
        import jax
        import jax.numpy as jnp

        import pathway_tpu as pw
        from pathway_tpu.models import hf_import
        from pathway_tpu.models.encoder import JaxEncoder
        from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import (
            SentenceTransformerEmbedder,
        )
        from pathway_tpu.xpacks.llm.llms import JaxChat
        from pathway_tpu.xpacks.llm.question_answering import (
            AdaptiveRAGQuestionAnswerer,
        )

        self.config, self.seed = config, seed
        self.words = config["corpus"]["doc_words"]
        self.preloaded = config["corpus"]["preloaded"]
        self._watch = compiles.CompileWatch()
        # -- encoder ------------------------------------------------------------
        enc_cfg = hf_import.config_from_hf(
            types.SimpleNamespace(**config["encoder"]["model"]))
        if "dtype" in config["encoder"]:  # the low-precision control only
            enc_cfg = dataclasses.replace(
                enc_cfg, dtype=jnp.dtype(config["encoder"]["dtype"]))
        self.enc_shape = {
            "vocab_size": enc_cfg.vocab_size, "d_model": enc_cfg.d_model,
            "n_layers": enc_cfg.n_layers, "n_heads": enc_cfg.n_heads,
            "d_ff": enc_cfg.d_ff, "max_len": enc_cfg.max_len,
            "ln_eps": float(enc_cfg.ln_eps)}
        self.enc_params = weights.transformer_params(
            self.enc_shape, seed + 1, embed_ln=True)
        self.enc_tok = tokenizer.WordHash(enc_cfg.vocab_size)
        emb = SentenceTransformerEmbedder(
            config=enc_cfg, seed=seed,
            device_resident=True if rehearse else None)
        if not emb.device_resident:
            raise RuntimeError("the embedder does not keep its vectors on "
                               "the device")
        emb._enc = JaxEncoder(enc_cfg, params=self.enc_params,
                              tokenizer=self.enc_tok)
        self._enc = emb._enc
        # -- corpus and document store ---------------------------------------------
        self.dir = tempfile.mkdtemp(prefix="pw_bench_docs_")
        self.stage = tempfile.mkdtemp(prefix="pw_bench_stage_")
        corpus.write_docs(self.dir, self.stage, seed, range(self.preloaded),
                          self.words)
        t_files = time.perf_counter()
        docs = pw.io.fs.read(self.dir, format="binary", mode="streaming",
                             with_metadata=True)
        store = DocumentStore(docs, retriever_factory=BruteForceKnnFactory(
            dimensions=emb.get_embedding_dimension(), embedder=emb))
        # -- generator ---------------------------------------------------------------
        dec = config["decoder"]
        dec_cfg = serve_engine.decoder_config(dec)
        self.dec_shape = serve_engine.decoder_shape(dec_cfg)
        self.dec_params = jax.block_until_ready(
            weights.transformer_params(self.dec_shape, seed))
        self.answer_tokens = dec["answer_tokens"]
        chat = JaxChat(config=dec_cfg, seed=seed, params=self.dec_params,
                       max_new_tokens=self.answer_tokens)
        self.dec_tok = tokenizer.WordHash(dec_cfg.vocab_size, log=1024)
        chat._lm.tokenizer = self.dec_tok
        # the first build fixes the engine's arguments; the answerer's own
        # probe then finds this instance
        self.engine = chat._lm.paged_engine(**dec.get("engine", {}))
        if self.engine is None:
            raise RuntimeError("paged_engine() returned None")
        if not rehearse and self.engine.attn != "pallas":
            raise RuntimeError("the engine did not choose attn='pallas'")
        self._chat = chat
        self._rag = AdaptiveRAGQuestionAnswerer(chat, store,
                                                llm_scheduler=True)
        # -- server --------------------------------------------------------------------
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        self.port = s.getsockname()[1]
        s.close()
        self._rag.build_server("127.0.0.1", self.port)
        self._run_err: list = []
        run = config["run"]

        def serve() -> None:
            try:
                pw.run(timeout_s=run["timeout_s"],
                       idle_stop_s=run["idle_stop_s"],
                       autocommit_duration_ms=run["autocommit_duration_ms"],
                       monitoring_level=pw.MonitoringLevel.NONE)
            except BaseException as exc:  # noqa: BLE001 - raised by check()
                self._run_err.append(exc)

        self._server = threading.Thread(target=serve, name="pw-run",
                                        daemon=True)
        self._server.start()
        self._alive = threading.Event()
        self._pinger = threading.Thread(target=self._ping, daemon=True)
        self._pinger.start()
        t_served = time.perf_counter()
        self.wait_indexed(self.preloaded)
        self.note("build", imports_models_files_s=t_files - t_0,
                  store_decoder_engine_server_s=t_served - t_files,
                  preload_indexed_s=time.perf_counter() - t_served)
        eng = self.engine
        self.info = {
            "engine.auto_config": eng.auto_config, "engine.attn": eng.attn,
            "decoder": self.dec_shape, "encoder": self.enc_shape,
            "kv_itemsize": eng.pool.k.dtype.itemsize,
            "corpus": config["corpus"], "port": self.port,
        }

    # -- what the generator drives ----------------------------------------------------
    def _ping(self) -> None:
        """pw.run stops after ``idle_stop_s`` without an event: that is how
        ``release`` ends it.  Until then one request a second keeps it up."""
        while not self._alive.wait(1.0):
            try:
                post(self.port, "/v1/statistics", {}, timeout=30)
            except OSError:
                pass

    def check(self) -> None:
        if self._run_err:
            raise self._run_err[0]

    def post(self, route: str, payload: dict, timeout: float = 300.0):
        return post(self.port, route, payload, timeout)

    def wait_indexed(self, want: int, deadline_s: float = 600.0) -> float:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < deadline_s:
            self.check()
            try:
                if post(self.port, "/v1/statistics", {},
                        timeout=60)["file_count"] == want:
                    return time.perf_counter() - t0
            except OSError:
                pass  # the server is still coming up
            time.sleep(0.25)
        raise RuntimeError(f"{want} documents were not indexed in "
                           f"{deadline_s} s")

    # -- what the harness reads ---------------------------------------------------------
    def counters(self) -> dict:
        st = self.engine.pool.stats
        out = {"llm." + k: float(getattr(st, k)) for k in LLM_COUNTERS}
        out["encoder.texts"] = float(self._enc.stats["texts"])
        out["encoder.calls"] = float(self._enc.stats["calls"])
        return out

    def samples(self, delta: dict) -> dict:
        return {}

    def compile_count(self) -> int:
        return self._watch.count()

    def release(self) -> None:
        """Let pw.run run idle and end, then drop the program's state."""
        self._alive.set()
        self._pinger.join()
        self._server.join(timeout=self.config["run"]["idle_stop_s"] + 60.0)
        self.check()
        self._chat._lm._paged_engine_inst = None
        self._chat = self._rag = self.engine = self._enc = None
        gc.collect()

    def close(self) -> None:
        self._alive.set()
        shutil.rmtree(self.dir, ignore_errors=True)
        shutil.rmtree(self.stage, ignore_errors=True)

    # -- correct ----------------------------------------------------------------------------
    def verify(self, observed: dict, seed: int) -> list:
        from benchmark.reference import encoder_f32, topk

        want = self.config["correct"]
        res = observed["result"]
        pre, cap = self.preloaded, observed["plan"]["probe_cap_s"]
        t_add: dict = {}
        t_del: dict = {}
        for c in res["changes"]:
            (t_add if c["kind"] == "add" else t_del)[c["doc"]] = c["t"]
        live = (set(range(pre)) | set(t_add)) - set(t_del)
        fin = res["final"]
        inputs = collections.Counter(fin["inputs"] or ())
        out = [
            {"name": "index_missing", "limit": 0,
             "value": len(live - set(inputs)) if fin["inputs"] is not None
             else len(live)},
            {"name": "index_extra", "limit": 0,
             "value": len(set(inputs) - live)},
            {"name": "index_duplicates", "limit": 0,
             "value": sum(c - 1 for c in inputs.values())},
            {"name": "count_mismatch", "limit": 0, "value": abs(
                (fin["file_count"] if fin["file_count"] is not None else -1)
                - len(live))},
            {"name": "own_not_first", "limit": 0, "value": sum(
                not hits or hits[0][0] != i for i, hits in fin["own"])},
            {"name": "deleted_returned", "limit": 0, "value": sum(
                ids is None or i in ids for i, ids in fin["gone"])},
        ]
        # exact KNN against the reference encoder and numpy
        ids = sorted(set(range(pre)) | set(t_add))
        row = {d: r for r, d in enumerate(ids)}
        E = encoder_f32.embed(
            self.enc_params, self.enc_shape,
            [self.enc_tok.encode(corpus.doc_text(seed, d, self.words))
             for d in ids])
        added = np.asarray([t_add.get(d, -np.inf) for d in ids])
        deleted = np.asarray([t_del.get(d, np.inf) for d in ids])
        worst = {"score_err": 0.0, "rank_violation": 0.0, "gone_returned": 0}
        wrong_k = 0
        replies = [r for r in res["retrieves"] if r["hits"] is not None]
        for r in replies:
            hits = [(row[d], s) for d, s in r["hits"] if d in row]
            wrong_k += len(hits) != observed["plan"]["k"]
            j = topk.judge_reply(
                E @ E[row[r["target"]]], hits,
                (added < r["sent"] - cap) & (deleted > r["done"]),
                deleted < r["sent"] - cap)
            worst["score_err"] = max(worst["score_err"], j["score_err"])
            worst["rank_violation"] = max(worst["rank_violation"],
                                          j["rank_violation"])
            worst["gone_returned"] += j["gone_returned"]
        for i, hits in fin["own"]:
            if hits:
                worst["score_err"] = max(worst["score_err"],
                                         abs(hits[0][1] - 1.0))
        out += [
            {"name": "wrong_k", "value": wrong_k, "limit": 0},
            {"name": "gone_in_replies", "value": worst["gone_returned"],
             "limit": 0},
            {"name": "retrieve_score_err", "value": worst["score_err"],
             "limit": want["retrieve_score_err"]["limit"],
             "replies": len(replies)},
            {"name": "retrieve_rank_violation",
             "value": worst["rank_violation"],
             "limit": want["retrieve_rank_violation"]["limit"]},
        ]
        # the answer path's decoder, on the answers the window served
        keep = self.dec_shape["max_len"] - self.answer_tokens
        prompts = {}
        for text, pids in self.dec_tok.log:
            prompts[text] = pids[-max(keep, 1):] or [4]
        done = []
        for a in res["answers"]:
            if a["error"] is not None:
                continue
            toks = tokenizer.parse_answer(a["text"])
            pids = next((p for t, p in prompts.items()
                         if a["question"] in t), None)
            done.append({"prompt": pids or [], "tokens": toks,
                         "n_out": self.answer_tokens, "known": pids is not None})
        out.append({"name": "answer_wrong_length", "limit": 0, "value": sum(
            len(d["tokens"]) != d["n_out"] or not d["known"] for d in done)})
        sample = serve_engine.sample_requests(
            [d for d in done if d["known"] and d["tokens"]],
            want["sample_answers"], seed)
        gap = serve_engine.gap_comparisons(
            self.dec_params, self.dec_shape, sample,
            want["answer_gap_per_near_tie"]["limit"])[0]
        gap["name"] = "answer_gap_per_near_tie"
        out.append(gap)
        restarts = observed["counters"].get("llm.engine_restarts", 0) \
            + observed["counters"].get("llm.engine_degraded", 0)
        out.append({"name": "engine_restarts", "value": restarts, "limit": 0})
        return out


def build(config: dict, seed: int, rehearse: bool) -> LiveRag:
    return LiveRag(config, seed, rehearse)
