"""Open-loop chat traffic through ``ServeEngine``.

Requests arrive on a fixed schedule whatever the engine is doing: the
inter-arrival gaps are the ``(j + 0.5) / N`` quantiles of an exponential at
``rate_per_s`` (a Poisson process's gaps), prompt and output lengths the
quantiles of lognormals (``prompt_median``/``prompt_sigma``,
``output_median``/``output_sigma``, clipped), so every seed sends the same
requests' sizes and gaps in another order, and the same number of them in
the window. Token ids are drawn from the seed.

The loop submits each request when it is due (``ServeEngine.submit``),
steps the engine while it holds work (``step``, then ``poll_completed``),
and sleeps until the next arrival when it holds none. A request's first
token is timed from when it was due to the end of the step that produced
it. After the window closes, nothing more is submitted and the engine runs
on until every request due in the window has finished (at most
``drain_limit_s`` more), so late answers count as late, not as missing.

Correctness: the benchmark makes the weights from the seed
(``chipbench/reference/qwen2.py``). A sample of the finished requests,
drawn from the seed and holding the longest, is run through the float32
reference over its prompt and served tokens; the number compared is the
widest gap by which a served token's logit lies below the reference's best
at that position. The control puts the float8 reference in the program's
place: at the same positions, the gap of the token the control ranks first.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.drivers.dma_rounds import lognormal_quantiles, seed_key
from chipbench.harness import Check, percentile
from chipbench.reference import qwen2
from chipbench.work import decode_flops

pc = time.perf_counter

#: (program config field, published config key) pairs that must agree.
_WIDTHS = (("num_layers", "num_hidden_layers"), ("d_model", "hidden_size"),
           ("num_heads", "num_attention_heads"),
           ("num_kv_heads", "num_key_value_heads"),
           ("d_ff", "intermediate_size"), ("vocab_size", "vocab_size"),
           ("rope_theta", "rope_theta"), ("norm_eps", "rms_norm_eps"),
           ("tie_embeddings", "tie_word_embeddings"))


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.m = ctx.cell.config
        self.s = self.m["serve"]
        self.t = ctx.cell.traffic
        self.seed = ctx.seed
        self.spans: Dict[str, List[float]] = {"step": [], "poll": [],
                                              "queue_wait": []}
        self.counts: Dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.setup_s = 0.0

    # -- set-up ---------------------------------------------------------
    def _program_config(self):
        from repro.configs import get_config
        cfg = get_config(self.s["registry_name"],
                         reduced=bool(self.s.get("registry_reduced")))
        cfg = dataclasses.replace(cfg, param_dtype=self.s["param_dtype"])
        for field, key in _WIDTHS:
            if getattr(cfg, field) != self.m[key]:
                raise ValueError(f"program config {field}="
                                 f"{getattr(cfg, field)!r} departs from the "
                                 f"published {key}={self.m[key]!r}")
        if cfg.head_dim_ != self.m["hidden_size"] // \
                self.m["num_attention_heads"] or not cfg.qkv_bias:
            raise ValueError("program attention departs from the config")
        return cfg

    def setup(self) -> None:
        from repro.models import param_shapes
        from repro.runtime import SubmitRequest
        from repro.serve import Request, ServeEngine

        self._SubmitRequest, self._Request = SubmitRequest, Request
        self.cfg = self._program_config()
        key = seed_key(self.seed)
        self.weights = qwen2.make_weights(jax.random.fold_in(key, 3), self.m,
                                          jnp.dtype(self.s["param_dtype"]))
        want = jax.tree.map(lambda x: (x.shape, x.dtype),
                            param_shapes(self.cfg))
        have = jax.tree.map(lambda x: (x.shape, x.dtype), self.weights)
        if want != have:
            raise ValueError("benchmark weights do not match the program's "
                             "parameter layout")
        self.engine = ServeEngine(
            self.weights, self.cfg, capacity=int(self.s["capacity"]),
            max_len=int(self.m["max_position_embeddings"]))
        self._uid = 10 ** 9
        # Warm-up: short requests through every slot, twice, so that every
        # program of the window (step, admission into each slot, argmax)
        # is compiled before it starts.
        cap = int(self.s["capacity"])
        for _ in range(2):
            for i in range(cap + 4):
                self._uid += 1
                self.engine.submit(SubmitRequest(request=Request(
                    uid=self._uid, prompt=[1 + i, 2 + i],
                    max_new_tokens=2)))
            while self.engine.queue or any(s.busy for s in self.engine.slots):
                self.engine.step()
                self.engine.poll_completed()
        jax.block_until_ready(self.engine.state.cur_pos)

    def _make_traffic(self, seconds: float) -> None:
        t = self.t
        rate = float(t["rate_per_s"])
        n = max(int(round(rate * seconds)), 1)
        rng = np.random.default_rng([self.seed, 5])
        gaps = rng.permutation(exponential_quantiles(n, rate))
        self.arrivals = np.cumsum(gaps)
        prompts = lognormal_quantiles(n, t["prompt_median"],
                                      t["prompt_sigma"], t["prompt_clip"])
        outputs = lognormal_quantiles(n, t["output_median"],
                                      t["output_sigma"], t["output_clip"])
        self.prompt_len = rng.permutation(np.round(prompts).astype(int))
        self.output_len = rng.permutation(np.round(outputs).astype(int))
        vocab = int(self.m["vocab_size"])
        self.prompts = [rng.integers(0, vocab, int(p)).tolist()
                        for p in self.prompt_len]

    # -- loop -----------------------------------------------------------
    def _submit(self, i: int) -> None:
        r = self._Request(uid=self._base + i, prompt=self.prompts[i],
                          max_new_tokens=int(self.output_len[i]))
        self.requests[r.uid] = r
        with jax.profiler.TraceAnnotation("submit"):
            self.engine.submit(self._SubmitRequest(request=r))

    def _step(self, count: bool) -> None:
        eng = self.engine
        pre = [s.request for s in eng.slots]
        t0 = pc()
        with jax.profiler.TraceAnnotation("step"):
            eng.step()
        t1 = pc()
        with jax.profiler.TraceAnnotation("poll"):
            eng.poll_completed()
        t2 = pc()
        post = [s.request for s in eng.slots]
        active = {r.uid: r for r in pre + post if r is not None}
        ctx = []
        for uid, r in active.items():
            if uid not in self.requests:
                continue
            if uid not in self.admitted:
                self.admitted[uid] = t1
            self.fed[uid] = self.fed.get(uid, 0) + 1
            ctx.append(self.fed[uid])
            times = self.token_times.setdefault(uid, [])
            times.extend([t1] * (len(r.output) - len(times)))
        if count:
            self.spans["step"].append(t1 - t0)
            self.spans["poll"].append(t2 - t1)
            self.window_flops += decode_flops(self.m, ctx)
            self.window_tokens += len(ctx)

    def window(self, seconds: float, *, start_clock: float) -> None:
        self.requests: Dict[int, object] = {}
        self.admitted: Dict[int, float] = {}
        self.fed: Dict[int, int] = {}
        self.token_times: Dict[int, List[float]] = {}
        self.window_flops = 0.0
        self.window_tokens = 0
        self._make_traffic(seconds)
        self._base = self._uid + 1          # engine uids of this window
        self._uid += len(self.arrivals) + 1
        self.t0 = t0 = pc()
        self.setup_s = t0 - start_clock
        n = len(self.arrivals)
        nxt = 0
        eng = self.engine
        while True:
            now = pc() - t0
            if now >= seconds:
                break
            while nxt < n and self.arrivals[nxt] <= now:
                self._submit(nxt)
                nxt += 1
            if eng.queue or any(s.busy for s in eng.slots):
                self._step(count=True)
            else:
                wake = self.arrivals[nxt] if nxt < n else seconds
                time.sleep(max(0.0, min(wake, seconds) - now))
        self.window_s = pc() - t0
        self.due = list(range(nxt))

    def finish(self) -> None:
        limit = pc() + float(self.t["drain_limit_s"])
        eng = self.engine
        while (eng.queue or any(s.busy for s in eng.slots)) and pc() < limit:
            self._step(count=False)
        self.attempted = len(self.due)
        done = set(eng.completed)
        b = self._base
        self.missing = [i for i in self.due
                        if b + i not in done
                        or len(self.requests[b + i].output)
                        != self.output_len[i]]
        self.failed = len(self.missing)
        ttft, itl, qwait = [], [], []
        for i in self.due:
            due = self.t0 + self.arrivals[i]
            times = self.token_times.get(b + i, [])
            ttft.append(times[0] - due if times and i not in self.missing
                        else math.inf)
            itl.extend(np.diff(times).tolist())
            if b + i in self.admitted:
                qwait.append(self.admitted[b + i] - due)
        self.ttft, self.itl = ttft, itl
        self.spans["queue_wait"] = qwait
        self.counts = {"requests": len(self.due), "flops":
                       self.window_flops, "tokens": self.window_tokens,
                       "steps": len(self.spans["step"]),
                       "window_s": self.window_s}

    def release(self) -> None:
        """Free the engine's caches; the weights stay for the reference."""
        self.served = {i: list(self.requests[self._base + i].output)
                       for i in self.due if i not in self.missing}
        self.engine = None

    # -- results --------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        return {"ttft_p90_ms": percentile(self.ttft, 90) * 1e3,
                "itl_p95_ms": percentile(self.itl, 95) * 1e3}

    def report_lines(self) -> List[str]:
        ttft = np.asarray([x for x in self.ttft if math.isfinite(x)]) * 1e3
        itl = np.asarray(self.itl) * 1e3
        steps = np.asarray(self.spans["step"]) * 1e3
        late = self.window_s - (self.arrivals[self.due[-1]]
                                if self.due else 0.0)
        return [
            f"cell: {self.ctx.cell.name}, rate {self.t['rate_per_s']} req/s, "
            f"{len(self.due)} requests due in {self.window_s:.6f} s, "
            f"{len(self.missing)} missing",
            f"ttft ms: median {np.median(ttft):.4f}, p90 "
            f"{np.percentile(ttft, 90):.4f}, max {ttft.max():.4f} "
            f"(n={ttft.size})" if ttft.size else "ttft: no samples",
            f"itl ms: median {np.median(itl):.4f}, p95 "
            f"{np.percentile(itl, 95):.4f} (n={itl.size})"
            if itl.size else "itl: no samples",
            f"steps in window: {steps.size}, median {np.median(steps):.4f} "
            f"ms, {self.window_tokens} slot-tokens, mean batch "
            f"{self.window_tokens / max(steps.size, 1):.2f}"
            if steps.size else "steps: none",
            f"generator: last arrival {late:.6f} s before the close",
            self.check_note,
        ]

    def checks(self) -> List[Check]:
        ids = sorted(self.served)
        k = min(int(self.t["check_requests"]), len(ids))
        longest = max(ids, key=lambda i: (self.prompt_len[i]
                                          + len(self.served[i]), -i))
        rng = np.random.default_rng([self.seed, 7])
        rest = [i for i in ids if i != longest]
        pick = [longest] + rng.choice(rest, size=k - 1,
                                      replace=False).tolist()
        width = int(self.t["check_width"])
        toks = np.zeros((len(pick), width), np.int32)
        where = []
        for row, i in enumerate(pick):
            seq = self.prompts[i] + self.served[i][:-1]
            toks[row, :len(seq)] = seq
            first = len(self.prompts[i]) - 1
            for j, tok in enumerate(self.served[i]):
                where.append((row, first + j, tok))
        rows = np.asarray([(r, p) for r, p, _ in where])
        served = np.asarray([t for _, _, t in where])
        gap = self._gap(toks, rows, served, fp8=False)
        self.check_note = (f"check: {len(where)} served tokens of "
                           f"{len(pick)} requests; program's widest gap "
                           f"{gap!r}")
        if self.ctx.control:
            gap = self._gap(toks, rows, served, fp8=True)
            self.check_note += f"; control's widest gap {gap!r}"
        return [Check("missing_requests", len(self.missing), 0),
                Check("served_gap_max", gap,
                      float(self.t["served_gap_limit"]))]

    def _gap(self, toks, rows, served, *, fp8: bool) -> float:
        """Widest gap (reference logits) below the reference's best of the
        served tokens, or with ``fp8`` of the control's first choices."""
        worst = 0.0
        block = int(self.t["check_block"])
        for lo in range(0, toks.shape[0], block):
            sel = (rows[:, 0] >= lo) & (rows[:, 0] < lo + block)
            t = jnp.asarray(toks[lo:lo + block])
            h = qwen2.hidden(self.weights, t, self.m)
            r = rows[sel]
            ref = qwen2.logits(self.weights,
                               h[r[:, 0] - lo, r[:, 1]])
            if fp8:
                hc = qwen2.hidden(self.weights, t, self.m, fp8=True)
                ctl = qwen2.logits(self.weights, hc[r[:, 0] - lo, r[:, 1]],
                                   fp8=True)
                pick = jnp.argmax(ctl, axis=-1)
            else:
                pick = jnp.asarray(served[sel])
            g = jnp.max(ref, axis=-1) - jnp.take_along_axis(
                ref, pick[:, None], axis=-1)[:, 0]
            worst = max(worst, float(jnp.max(g)))
        return worst
