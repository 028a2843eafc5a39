"""The entry the measured window drives, composed of the program's parts.

The program has no per-request serving entry, so this is the benchmark's
copy of ``repro.launch.serve.run_server``'s loop, built only from what
``run_server`` builds from: ``serving_config`` and ``build_model``, the
jitted steps of ``serving.engine.make_prefill_fn`` and ``make_decode_fn``
(decode with the cache donated), greedy sampling, and a batching policy of
``serving.batching.make_policy``.  Batching is request-level: the policy
picks a batch, the batch is prefilled on its prompts right-padded to the
cell's length with their true lengths, and then decodes until its longest
request is done.  Sampling runs inside the same jitted call as its step.

Per request it records ``due`` (scheduled send), ``dispatch``,
``first_token`` (the prefill's sampled tokens are on the host: the one
sync per batch a streaming server makes) and ``done`` (the batch's last
tokens are on the host, when ``run_server`` returns a request), all in
seconds from the window's start, and the tokens it served.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights
from bench.generator import Request
from repro.configs import get_config
from repro.models import ModelConfig, build_model
from repro.serving.batching import QueuedRequest, make_policy
from repro.serving.engine import (greedy_sample, make_decode_fn,
                                  make_prefill_fn, serving_config)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
span = jax.profiler.TraceAnnotation


def model_config(config: Dict) -> ModelConfig:
    """The catalog's configuration with the file's sizes, the keys of its
    family's ``CATALOG_KEYS``; a size that differs from the catalog must be
    listed in the file's ``reduced``."""
    keys = spec.family(config["family"]).CATALOG_KEYS
    base = get_config(config["catalog"])
    sizes = {ours: config[theirs] for theirs, ours in keys.items()}
    for theirs, ours in keys.items():
        if sizes[ours] != getattr(base, ours) and theirs not in config["reduced"]:
            raise ValueError(f"{theirs} differs from the catalog's "
                             f"{config['catalog']} but is not in 'reduced'")
    dt = config["dtype"]
    return dataclasses.replace(base, dtype=dt, serve_param_dtype=dt, **sizes)


def params_fn(config: Dict, dims: Dict) -> Callable:
    """Seed words to the program's weights, as one jitted call."""
    fam = spec.family(config["family"])
    dtype = jnp.dtype(config["dtype"])
    return jax.jit(lambda w: fam.program_params(
        weights.all_layers(w, dims, dtype, fam)))


@dataclasses.dataclass
class Record:
    req: Request
    dispatch: float
    first_token: float = 0.0
    done: float = 0.0
    tokens: Optional[np.ndarray] = None


@dataclasses.dataclass
class Batch:
    lengths: List[int]      # true prompt lengths
    outs: List[int]         # tokens owed per row
    dispatch: float
    done: float = 0.0
    traced: bool = False


class Server:
    """The model's two jitted steps, with sampling, at one cell's shapes."""

    def __init__(self, cfg: ModelConfig, params, pad: int, max_len: int):
        model = build_model(serving_config(cfg))
        prefill = make_prefill_fn(model, max_len=max_len)
        decode = make_decode_fn(model)

        def serve_prefill(params, tokens, lengths):
            cache, logits = prefill(params, tokens, lengths)
            return cache, greedy_sample(logits)

        def serve_decode(params, cache, tokens):
            cache, logits = decode(params, cache, tokens)
            return cache, greedy_sample(logits)

        want = jax.eval_shape(model.init, jax.random.key(0))
        got = jax.eval_shape(lambda: params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("weights do not match the program's tree")
        self.params = params
        self.pad = pad
        self._prefill = jax.jit(serve_prefill)
        self._decode = jax.jit(serve_decode, donate_argnums=(1,))

    def warm(self, sizes) -> None:
        """Compile (or load) and run each batch size's prefill and decode."""
        for b in sizes:
            toks = jnp.zeros((b, self.pad), jnp.int32)
            cache, tok = self._prefill(self.params, toks,
                                       jnp.full((b,), self.pad, jnp.int32))
            cache, tok = self._decode(self.params, cache, tok)
            jax.block_until_ready(tok)
            del cache

    def run_batch(self, reqs: List[Request], clock: Callable[[], float],
                  recs: List[Record]) -> None:
        """Serve one batch; fills the records' times and tokens."""
        b = len(reqs)
        with span("bench.inputs"):
            toks = np.zeros((b, self.pad), np.int32)
            for i, r in enumerate(reqs):
                toks[i, :len(r.prompt)] = r.prompt
            lens = np.array([len(r.prompt) for r in reqs], np.int32)
            toks, lens = jnp.asarray(toks), jnp.asarray(lens)
        with span("bench.prefill"):
            cache, tok = self._prefill(self.params, toks, lens)
        with span("bench.sync"):
            jax.device_get(tok)
        t_first = clock()
        out = [tok]
        with span("bench.decode"):
            for _ in range(max(r.out_len for r in reqs) - 1):
                cache, tok = self._decode(self.params, cache, tok)
                out.append(tok)
        with span("bench.sync"):
            host = np.stack(jax.device_get(out), axis=1)
        t_done = clock()
        del cache
        for i, rec in enumerate(recs):
            rec.first_token, rec.done = t_first, t_done
            rec.tokens = host[i, :rec.req.out_len]


class CompileCounter:
    """Backend compilations while registered (``jax.monitoring`` events)."""

    def __init__(self):
        self.count = 0
        self._on = False

    def _listener(self, event: str, duration: float, **kw) -> None:
        if self._on and event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        self._on = True
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc):
        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._listener)


class Tracer:
    """Profiles a stretch of the window: from the first batch that starts
    at or after ``start_s`` to the end of the first batch that ends at or
    after ``stop_s``.  Both edges fall between batches, when nothing is in
    flight, so every device event in the trace belongs to a traced batch."""

    def __init__(self, directory: Optional[str], start_s: float, stop_s: float):
        self.directory, self.start_s, self.stop_s = directory, start_s, stop_s
        self.state = "off" if directory is None else "waiting"

    def before_batch(self, now: float) -> bool:
        if self.state == "waiting" and now >= self.start_s:
            jax.profiler.start_trace(self.directory)
            self.state = "on"
        return self.state == "on"

    def after_batch(self, now: float) -> None:
        if self.state == "on" and now >= self.stop_s:
            jax.profiler.stop_trace()
            self.state = "done"

    def close(self) -> None:
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"


class Loop:
    """Drives a Server with one cell's traffic and keeps the records."""

    def __init__(self, server: Server, batcher: Dict, tracer: Tracer):
        self.server = server
        self.policy = make_policy(batcher["policy"],
                                  preferred=tuple(batcher["preferred"]))
        self.tracer = tracer
        self.records: List[Record] = []
        self.batches: List[Batch] = []
        self.t0 = 0.0

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def _serve(self, queue: List[QueuedRequest]) -> List[Record]:
        with span("bench.batch"):
            now = self.clock()
            picked, _ = self.policy.next_batch(queue, now, now)
            ids = {id(q) for q in picked}
            queue[:] = [q for q in queue if id(q) not in ids]
        reqs = [q.request for q in picked]
        traced = self.tracer.before_batch(self.clock())
        batch = Batch(dispatch=self.clock(), lengths=[len(r.prompt) for r in reqs],
                      outs=[r.out_len for r in reqs], traced=traced)
        recs = [Record(req=r, dispatch=batch.dispatch) for r in reqs]
        self.server.run_batch(reqs, self.clock, recs)
        batch.done = recs[0].done
        self.tracer.after_batch(batch.done)
        self.batches.append(batch)
        self.records += recs
        return recs

    def open_loop(self, requests: List[Request], drain_s: float) -> None:
        """Send each request at its due time; after the last, serve what is
        queued until ``drain_s`` past the window's start."""
        pending = collections.deque(requests)
        queue: List[QueuedRequest] = []
        self.t0 = time.perf_counter()
        while (pending or queue) and self.clock() < drain_s:
            with span("bench.admit"):
                now = self.clock()
                while pending and pending[0].due <= now:
                    r = pending.popleft()
                    queue.append(QueuedRequest(request=r, enqueue_s=r.due))
            if queue:
                self._serve(queue)
                continue
            with span("bench.wait"):
                time.sleep(max(pending[0].due - self.clock(), 0.0))

    def backlog(self, requests: Iterator[Request], outstanding: int,
                seconds: float) -> None:
        """Keep ``outstanding`` requests waiting; send one more as each
        returns, until the window closes."""
        self.t0 = time.perf_counter()
        queue = [QueuedRequest(request=next(requests), enqueue_s=0.0)
                 for _ in range(outstanding)]
        while self.clock() < seconds:
            for rec in self._serve(queue):
                with span("bench.admit"):
                    r = next(requests)
                    r.due = rec.done
                    queue.append(QueuedRequest(request=r, enqueue_s=r.due))
