"""The ``serve`` entry: one chip, the continuous-batching engine a user
builds (``apex_tpu.serving.ServingEngine``, its settings from the cell
file's ``engine`` block) under open-loop traffic (``serve_traffic.py``).

``setup`` makes the weights on the device from the seed in one jitted call
(the reference's own generator, in the program's layout, cast to bfloat16
as a user serving the model would), builds the engine and sends one request
through each prompt bucket the cell's traffic can reach, so that every
prefill, insert, sample and decode program is compiled or loaded before the
traffic starts.  ``window`` is one host loop: submit every request whose due
time has passed, call ``engine.step()``, repeat.  Before it, the mix's
``fill`` requests go in one before each step, so that the lanes fill one a
step, and the arrivals start the fill's ``seconds`` before the window
opens: the window finds the engine as the traffic keeps it.  All of that
counts as set-up.
The window closes ``seconds`` after it opened, and what is still queued or
decoding then is left.  ``check`` sets a seeded sample of the requests the
window completed against the float32 reference (``reference/serve.py``)
once the engine is freed.

The end-to-end number, ``serve_tokens_per_s``: the output tokens of the
requests that the engine completed inside the window, as the host saw
them come back from ``step()``, over the window's seconds.  The loop's host
spans (``bench:submit``, ``bench:step``, ``bench:stats``, ``bench:idle``)
name the device's idle gaps in a traced run.
"""

from __future__ import annotations

import functools
import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchmark import flops_serve, serve_traffic, trace_reduce
from benchmark.entries import train
from benchmark.reference import serve as ref_serve
from benchmark.reference import train as ref_train

TRACED_SHARE = (0.3, 0.6)   # the traced slice, as shares of the window
BF16 = jnp.bfloat16


def setup(config: dict, cell: dict, seed: int, devices: list, t0: float,
          log) -> "ServeRun":
    if cell["chips"] != 1 or len(devices) != 1:
        raise SystemExit("benchmark: the serve entry drives one chip")
    return ServeRun(config, cell, seed, devices[0], t0, log)


class ServeRun:
    def __init__(self, config, cell, seed, device, t0, log, prepare=None):
        """``prepare``: what the weights go through before the engine
        takes them (a control's lower precision; none in a run)."""
        from apex_tpu.serving import ServingEngine

        self.config, self.cell, self.seed, self.device, self.log = (
            config, cell, seed, device, log)
        self.t0 = t0
        self.key = train.seed_key(seed)
        model = ref_train.model_module(config["reference"]["model"])
        program = config["program"]
        self.cfg = train._load(program["model_config"])(
            **program["model_config_kwargs"])
        self.make_reference_params = lambda: jax.jit(
            lambda key: ref_serve.as_served(model.init_params(key, config)))(
                self.key)
        log("setup: bfloat16 weights on the device")
        params = jax.jit(lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(BF16), model.init_params(key, config)))(
                self.key)
        if prepare is not None:
            params = prepare(params)
        jax.block_until_ready(params)
        self.params = params
        self.engine = ServingEngine(params, self.cfg, **cell["engine"])
        self.buckets = self.engine.buckets
        self._warm(cell["traffic_mix"])
        self.setup_s = None
        self.served = None

    def _warm(self, mix: dict) -> None:
        """One request through each prompt bucket the mix reaches, twice:
        prefill, K/V insert and first-token sample per bucket, the decode
        step, and the eager updates between them."""
        lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
        ladder = self.buckets
        lengths = [min(b, hi) for j, b in enumerate(ladder)
                   if b >= lo and (j == 0 or ladder[j - 1] < hi)]
        rng = np.random.default_rng(0)
        for _ in range(2):
            for n in lengths:
                self.engine.submit(
                    rng.integers(0, self.config["vocab_size"], n,
                                 dtype=np.int32),
                    max_new_tokens=2, temperature=0.0)
            self.engine.run()
        self.log(f"setup: warmed prompt lengths {lengths}")

    def window(self, seconds: float, trace_dir) -> dict:
        mix = self.cell["traffic_mix"]
        sched = serve_traffic.make_schedule(
            mix, self.config["vocab_size"], self.seed % 2 ** 64, seconds)
        due = sched.due_s                 # the window opens at 0
        engine = self.engine
        n = len(due)
        index_of, done_at, responses, refused = {}, {}, {}, []
        # one row a step: start, end (host clock, seconds after the window
        # opens), requests it admitted, lanes it decoded, requests queued
        # after it, K/V blocks in use after it
        steps = []
        trace_at = [s * seconds for s in TRACED_SHARE]
        tracing = traced = None
        queued = 0
        i = 0
        filled = int(np.sum(np.isneginf(due)))
        t0 = time.perf_counter()
        while i < filled:             # the fill: one request a step
            index_of[engine.submit(
                sched.prompts[i], max_new_tokens=int(sched.output_tokens[i]),
                temperature=0.0)] = i
            i += 1
            for r in engine.step():
                responses[index_of[r.request_id]] = r
        lead = mix["fill"]["seconds"] if filled else 0.0
        origin = time.perf_counter() + lead
        self.log(f"window: {filled} requests filled the lanes one a step in "
                 f"{time.perf_counter() - t0:.2f} s")
        self.log(f"window: {n - filled} more due from {lead} s before the "
                 f"{seconds} s window")
        while True:
            now = time.perf_counter() - origin
            while i < n and due[i] <= now:
                try:
                    with TraceAnnotation("bench:submit"):
                        rid = engine.submit(
                            sched.prompts[i],
                            max_new_tokens=int(sched.output_tokens[i]),
                            temperature=0.0)
                    index_of[rid] = i
                    queued += 1
                except ValueError:
                    refused.append(i)
                i += 1
            if trace_dir is not None:
                if tracing is None and now >= trace_at[0]:
                    jax.profiler.start_trace(trace_dir)
                    tracing = [time.perf_counter() - origin, None]
                elif tracing and tracing[1] is None and now >= trace_at[1]:
                    jax.profiler.stop_trace()
                    tracing[1] = time.perf_counter() - origin
                    traced = tracing
            if now >= seconds:
                break
            if engine.idle:
                with TraceAnnotation("bench:idle"):
                    time.sleep(max(0.0, min(due[i] if i < n else seconds,
                                            seconds) - now))
                continue
            t = time.perf_counter() - origin
            with TraceAnnotation("bench:step"):
                done = engine.step()
            t_end = time.perf_counter() - origin
            with TraceAnnotation("bench:stats"):
                stats = engine.stats()
            steps.append((t, t_end, queued - stats["queued"],
                          stats["active"] + len(done), stats["queued"],
                          stats["blocks_in_use"]))
            queued = stats["queued"]
            for r in done:
                responses[index_of[r.request_id]] = r
                done_at[index_of[r.request_id]] = t_end
        if tracing and tracing[1] is None:
            jax.profiler.stop_trace()
            tracing[1] = time.perf_counter() - origin
            traced = tracing
        self.setup_s = origin - self.t0
        inside = sorted(k for k, t in done_at.items() if 0.0 < t <= seconds)
        record = self._record(sched, inside, responses, steps, refused,
                              filled, seconds)
        if traced is not None:
            record.update(self._read_trace(trace_dir, traced, record))
        self.log(f"window: {len(inside)} requests completed inside it, "
                 f"{len(responses)} since the fill began; "
                 f"{engine.stats()['queued']} queued at its close")
        self.served = (sched, responses)
        return record

    def _record(self, sched, inside, responses, steps, refused, filled,
                seconds) -> dict:
        for r in responses.values():
            if r.tokens.size != 1 + r.decode_steps:
                raise SystemExit(
                    f"benchmark: request {r.request_id} came back with "
                    f"{r.tokens.size} tokens from {r.decode_steps} decode "
                    "steps; the window counts one token an admission and "
                    "one a decoding lane a step")
        steps = np.asarray(steps, np.float64).reshape(-1, 6)
        ends = steps[:, 1]
        before = ends <= 0.0
        opened = float(ends[before].max()) if before.any() else 0.0
        within = (ends > 0.0) & (ends <= seconds)
        rows = steps[within]
        # each step gives one token to each request it admits (sampled
        # from the prefill) and one to each lane it decodes
        tokens = float(rows[:, 2].sum() + rows[:, 3].sum())
        span = float(rows[-1, 1]) - opened if len(rows) else 0.0
        # K/V the step's lanes held: the blocks in use after it, whole
        # blocks (the unit the paged kernel reads)
        ctx = rows[:, 5] * self.engine.block_size
        # the engine admits in the order of submission: the window's
        # admissions are the next ones after the fill and those before it
        skip = set(refused)
        order = [j for j in range(len(sched.due_s)) if j not in skip]
        a = filled + int(steps[before, 2].sum())
        admitted = order[a:a + int(rows[:, 2].sum())]
        flops = (sum(flops_serve.decode_flops(self.config, int(l), int(c))
                     for l, c in zip(rows[:, 3], ctx))
                 + sum(flops_serve.prefill_flops(self.config,
                                                 len(sched.prompts[j]))
                       for j in admitted))
        e2e = {"setup_s": self.setup_s,
               "serve_tokens_per_s": tokens / span if span > 0 else 0.0}
        self.log(f"window: {len(rows)} steps, {tokens:.0f} tokens over "
                 f"{span:.4f} s; " + ", ".join(
                     f"{k} {v:.6g}" for k, v in e2e.items()))
        return {
            "end_to_end": e2e,
            "attempted": len(inside) + len(refused),
            "failed": len(refused),
            "window_s": span,
            "serve": {
                "max_slots": self.engine.max_slots,
                "step_s": rows[:, 1] - rows[:, 0],
                "step_start_s": rows[:, 0],
                "admitted": rows[:, 2],
                "decoded_lanes": rows[:, 3],
                "queued_after": rows[:, 4],
                "ctx_sum": ctx,
                "flops": flops,
                "prefill_ms": [responses[k].prefill_ms for k in inside],
            },
            # None off the chip: the CPU keeps no such count
            "memory_peak_bytes": (self.device.memory_stats() or {}).get(
                "peak_bytes_in_use"),
        }

    def _read_trace(self, trace_dir, traced, record) -> dict:
        """The traced slice's reduction, and the cell's decode kernels'
        own time in it beside the decode steps it holds."""
        events = trace_reduce.load_events(trace_reduce.find_xplane(trace_dir))
        reduced = trace_reduce.reduce_events(events)
        if not reduced:
            return {}
        own = trace_reduce._self_times(
            [e for e in events if e.line == trace_reduce.OPS_LINE])
        serve = record["serve"]
        inside = ((serve["step_start_s"] >= traced[0])
                  & (serve["step_start_s"] + serve["step_s"] <= traced[1])
                  & (serve["decoded_lanes"] > 0))
        kernel = self.cell["decode_kernel"]
        least = sum(flops_serve.decode_kernel_bytes(
            self.config, int(l), int(c), kernel["with_projection"])
            for l, c in zip(serve["decoded_lanes"][inside],
                            serve["ctx_sum"][inside]))
        return {"trace": reduced, "decode_kernel": {
            "own_s": sum(own.get(name, 0.0) for name in kernel["ops"]) / 1e9,
            "steps": int(inside.sum()),
            "least_bytes": least,
        }}

    def sample(self) -> list:
        """The requests the check compares, drawn from the seed among those
        completed by the window's close: the longest (prompt and output)
        and the rest at random, ``sample_requests`` in all (at most the
        engine's lanes)."""
        sched, responses = self.served
        done = sorted(responses)
        if not done:
            return []
        rng = np.random.default_rng([self.seed % 2 ** 64, 1])
        longest = max(done, key=lambda i: len(sched.prompts[i])
                      + responses[i].tokens.size)
        rest = [i for i in done if i != longest]
        picked = [longest] + list(rng.permutation(rest)[
            :self.cell["check"]["sample_requests"] - 1])
        return [(sched.prompts[i], responses[i].tokens) for i in picked]

    def free(self) -> None:
        """Drops the engine and its weights, so that the reference runs on
        a chip that holds nothing of the program's."""
        self.engine = None
        self.params = None
        gc.collect()

    def departures(self) -> int:
        """Leaves of the served weights and K/V pools whose type is not the
        one the cell file's ``served_precision`` states: a run that serves
        the model otherwise is no sound run, whatever its numbers read."""
        stated = self.cell["served_precision"]
        weights = jnp.dtype(stated["weights"])
        kv = jnp.dtype(stated["kv_cache"])
        return (sum(1 for x in jax.tree_util.tree_leaves(self.params)
                    if x.dtype != weights)
                + sum(1 for k in ("k", "v") if self.engine.cache[k].dtype != kv))

    def check(self) -> tuple:
        """The program's log-probability of every served token of a seeded
        sample of the completed requests, read through the program's own
        prefill, paged insert and paged decode step with the served tokens
        fed back, as many lanes in use as the sample holds (the engine's
        lanes at most); then, with the program freed, the float32 reference
        over the same rows.  The numbers: the widest gap by which a served
        token's logit lies below the reference's best; the widest gap, and
        the root mean square, between the program's log-probability of a
        served token and the reference's; the leaves served in another
        type than the cell states."""
        rows = self.sample()
        departures = self.departures()
        if not rows:
            self.free()
            return False, {}
        spec = dict(self.cell["engine"])
        self.engine = None
        gc.collect()
        t = time.perf_counter()
        program = forced_logprobs(self.params, self.cfg, spec, self.buckets,
                                  rows)
        self.free()
        self.log(f"check: the program's log-probabilities over "
                 f"{len(rows)} requests, {sum(len(s) for _, s in rows)} "
                 f"served tokens, {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        reads = ref_serve.served_reads(self.make_reference_params(),
                                       self.config, rows, _length(rows))
        margin = np.concatenate([best - picked
                                 for best, picked, _ in reads])
        gap = np.concatenate([np.abs(lp - (picked - lse))
                              for lp, (_, picked, lse) in zip(program, reads)])
        numbers = {"argmax_margin_max": float(margin.max()),
                   "logprob_gap_max": float(gap.max()),
                   "logprob_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
                   "precision_departures": float(departures),
                   "tokens_compared": float(gap.size)}
        self.log(f"check: float32 reference, "
                 f"{time.perf_counter() - t:.1f} s")
        return ref_train.judge(numbers, self.cell["check"]["limits"])


def _length(rows) -> int:
    """One padded length for the reference's rows, a multiple of 128."""
    return -(-max(len(p) + len(s) for p, s in rows) // 128) * 128


@functools.lru_cache(maxsize=None)
def _forced_step(cfg, decode_fused: str):
    """The engine's paged decode step (``decode_step`` over the pool, the
    lanes' positions advanced where live) with the served token fed in
    and its successor's log-probability read out where the engine
    samples."""
    generate = importlib.import_module("apex_tpu.models.generate")

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def step(params, cache, tables, tokens, targets, active):
        prev = cache["pos"]
        logits, new = generate.decode_step(
            params, tokens, dict(cache, block_tables=tables), cfg,
            decode_fused=decode_fused)
        cache = {k: v for k, v in new.items()
                 if k not in ("pos", "block_tables")}
        cache["pos"] = jnp.where(active, new["pos"], prev)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(lp, targets[:, None], axis=-1)[:, 0], cache

    return step


def forced_logprobs(params, cfg, spec: dict, buckets, rows) -> list:
    """For each of ``rows`` = ``[(prompt, served tokens)]``, one to a lane
    of an engine of ``spec``'s lanes and pool: the program's log-probability
    of each served token, its first from the prompt's prefill at its
    bucket, each later one from the decode step that the token before it
    is fed to."""
    from apex_tpu.models.generate import prefill
    from apex_tpu.ops.decode_step import route_decode_fused
    from apex_tpu.serving import paged_cache as pc
    from apex_tpu.serving.batching import pad_prompt, pick_bucket

    slots, bs = spec["max_slots"], spec["block_size"]
    nb = spec["num_blocks"]
    if len(rows) > slots:
        raise ValueError(f"{len(rows)} rows for {slots} lanes")
    wire = pc.resolve_cache_wire(spec.get("cache_wire"))
    cache = pc.init_paged_pool(cfg, nb, bs, cache_wire=wire)
    tables = np.full((slots, pc.blocks_for(spec["max_len"], bs)), nb,
                     np.int32)
    pos = np.zeros((slots,), np.int32)
    out = [[] for _ in rows]
    dtype = jnp.dtype(cfg.compute_dtype)
    free = 0
    for lane, (prompt, served) in enumerate(rows):
        n = len(prompt)
        bucket = pick_bucket(n, buckets)
        held = pc.blocks_for(n + len(served), bs)
        tables[lane, :held] = np.arange(free, free + held)
        wid = np.full((pc.blocks_for(bucket, bs),), nb, np.int32)
        wid[:pc.blocks_for(n, bs)] = tables[lane, :pc.blocks_for(n, bs)]
        free += held
        logits, small = prefill(
            params, jnp.asarray(pad_prompt(prompt, bucket)[None]), cfg,
            prompt_lens=jnp.asarray([n], jnp.int32), max_len=bucket,
            cache_dtype=dtype)
        if wire == "int8":
            k, v, ks, vs = pc.paged_insert_prefill_q(
                cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
                small["k"], small["v"], jnp.asarray(wid), jnp.int32(n),
                block_size=bs)
            cache = {"k": k, "v": v, "k_scale": ks, "v_scale": vs}
        else:
            k, v = pc.paged_insert_prefill(
                cache["k"], cache["v"], small["k"], small["v"],
                jnp.asarray(wid), jnp.int32(n), block_size=bs)
            cache = {"k": k, "v": v}
        lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)[0])
        out[lane].append(float(lp[int(served[0])]))
        pos[lane] = n
    if free > nb:
        raise ValueError(f"the rows hold {free} blocks, the pool {nb}")
    step = _forced_step(cfg, route_decode_fused(None))
    cache["pos"] = jnp.asarray(pos)
    tables = jnp.asarray(tables)
    for t in range(max(len(s) for _, s in rows) - 1):
        tokens = np.zeros((slots,), np.int32)
        targets = np.zeros((slots,), np.int32)
        active = np.zeros((slots,), bool)
        for lane, (_, served) in enumerate(rows):
            if t + 1 < len(served):
                tokens[lane], targets[lane] = served[t], served[t + 1]
                active[lane] = True
        lp, cache = step(params, cache, tables, jnp.asarray(tokens),
                         jnp.asarray(targets), jnp.asarray(active))
        lp = np.asarray(lp)
        for lane in np.flatnonzero(active):
            out[lane].append(float(lp[lane]))
    del cache
    return [np.asarray(o, np.float64) for o in out]
