"""Async continuous-batching serving front end (ISSUE 6 tentpole b) —
the FastGen/MII serving layer over ``InferenceEngineV2``.

``AsyncInferenceServer`` runs the shared scheduler
(:class:`~..inference.v2.serve_loop.FusedServeLoop` — the same driver
``generate_fused`` uses closed-loop) on a dedicated worker thread and
exposes an asyncio surface:

- ``await server.submit(prompt, ...)`` returns a
  :class:`RequestHandle` that async-iterates the request's tokens as
  the drain thread lands them (per-request streaming);
- priority tiers (lower value = runs first) with optional PREEMPTION:
  a high-priority prompt that cannot be admitted parks
  strictly-lower-priority running requests — their KV blocks swap out
  through the ref-counted allocator (prefix-cached full blocks stay
  warm in the LRU), their token history stays host-side, and they
  resume position-exactly;
- ``handle.cancel()`` mid-stream releases the request's KV blocks at
  the next dispatch boundary (no leak);
- TTFT/ITL histograms, queue-depth gauges and scheduler counters flow
  through the telemetry registry, and each scheduler step heartbeats
  the flight recorder, so a wedged serving loop leaves a dump behind.

The worker thread owns every engine/JAX call; asyncio-side methods only
exchange messages with it (a mailbox + wake event), so the event loop
never blocks on device work.
"""

from __future__ import annotations

import asyncio
import itertools
import threading
import time
from collections import deque
from typing import Optional, Sequence

from ..inference.v2.serve_loop import (LOOP_COUNTER_KEYS, FusedServeLoop,
                                       TokenEvent)
from ..utils.logging import log_dist
from ..utils.telemetry_probe import active_telemetry as _telemetry
from .config import ServingConfig

_DONE = object()


def _slo_seconds(cfg: ServingConfig):
    """``ServingConfig`` SLO targets (milliseconds, the user-facing
    unit) -> ``RequestTraceRecorder.set_slo`` arguments (seconds, the
    recorder's unit). THE one place the ms->s conversion happens —
    unit-boundary regression test in tests/test_fleet.py. 0 disables a
    target (maps to None)."""
    return (cfg.slo_ttft_ms / 1e3 if cfg.slo_ttft_ms else None,
            cfg.slo_itl_ms / 1e3 if cfg.slo_itl_ms else None)


class RequestCancelled(Exception):
    """Raised by the stream iterator of a cancelled request."""


class RequestFailed(Exception):
    """Raised by the stream iterator when the scheduler rejected the
    request (e.g. a prompt that can never fit the KV pool)."""


class RequestHandle:
    """Per-request streaming handle: ``async for tok in handle`` yields
    int token ids as they decode; ``await handle.tokens()`` collects
    the full generation. Created by
    :meth:`AsyncInferenceServer.submit`."""

    def __init__(self, uid: int, server: "AsyncInferenceServer"):
        self.uid = uid
        self._server = server
        self._q: asyncio.Queue = asyncio.Queue()
        self._buf: deque = deque()
        self._finished = False
        self.error: Optional[str] = None
        self.submitted_at = time.perf_counter()
        # request-trace correlation id (ISSUE 10): set by submit() when
        # telemetry's request tracing is active — the same id appears
        # in the access log, the Perfetto request track and the
        # Prometheus histogram exemplars
        self.trace_id: Optional[str] = None

    # worker -> event loop (always via call_soon_threadsafe)
    def _push(self, evt: TokenEvent) -> None:
        if evt.tokens:
            self._q.put_nowait(list(evt.tokens))
        if evt.finished:
            self.error = evt.error
            self._q.put_nowait(_DONE)

    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        while not self._buf:
            if self._finished:
                raise StopAsyncIteration
            item = await self._q.get()
            if item is _DONE:
                self._finished = True
                if self.error == "cancelled":
                    raise RequestCancelled(f"request {self.uid}")
                if self.error:
                    raise RequestFailed(self.error)
                raise StopAsyncIteration
            self._buf.extend(item)
        return self._buf.popleft()

    async def tokens(self) -> list[int]:
        """Collect the remaining stream into one list."""
        return [t async for t in self]

    def cancel(self) -> None:
        """Drop the request; its KV blocks are released at the next
        dispatch boundary. The stream raises
        :class:`RequestCancelled`."""
        self._server._post(("cancel", self.uid))


class AsyncInferenceServer:
    """See module docstring. Typical use::

        engine = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            fused_admission=True, max_inflight_dispatches=4, ...))
        async with AsyncInferenceServer(engine) as server:
            h = await server.submit(prompt_ids, max_new_tokens=256)
            async for tok in h:
                ...
    """

    def __init__(self, engine, config=None):
        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig(**config)
        self.engine = engine
        self.config = config
        self._uid = itertools.count()
        self._handles: dict[int, RequestHandle] = {}
        self._mailbox: list[tuple] = []
        self._mail_lock = threading.Lock()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._aloop: Optional[asyncio.AbstractEventLoop] = None
        self._accepting = False
        self._stopping = False
        self._open = 0          # queued + running requests
        # live admission bound (ISSUE 19): submits arriving with this
        # many requests open are SHED (fast-fail, counted). Written by
        # the config at start and by the controller on the worker
        # thread, read by submit() on the event loop — a GIL-atomic
        # int whose staleness costs one admit/shed decision, never
        # correctness
        self._shed_depth = int(config.shed_queue_depth)  # graftlint: disable=GL052
        self._shed_count = 0    # event-loop-thread owned (like _open)
        self._controller = None     # online feedback loop (ISSUE 19)
        self._worker_error: Optional[BaseException] = None
        self.session: Optional[FusedServeLoop] = None
        self._rt = None         # request-trace recorder (ISSUE 10)
        self._hb_meta: dict = {}    # cached heartbeat summary
        self._hb_next = 0.0         # next full-summary refresh time
        self._health_next = 0.0     # next health quality-input refresh
        self._beat_next = 0.0       # next liveness heartbeat forward

    # ------------------------------------------------------------------
    async def __aenter__(self):
        await self.start()
        return self

    async def __aexit__(self, *exc):
        await self.stop(drain=exc[0] is None)

    async def start(self) -> None:
        if self._thread is not None:
            raise RuntimeError("server already started")
        cfg = self.config
        self._aloop = asyncio.get_running_loop()
        self.session = FusedServeLoop(
            self.engine, k_steps=cfg.k_steps,
            temperature=cfg.temperature, top_k=cfg.top_k,
            top_p=cfg.top_p, eos_id=cfg.eos_token_id, seed=cfg.seed,
            strict=False, preemption=cfg.preemption,
            replica=cfg.replica)
        tel = _telemetry()
        self._rt = (tel.get_request_recorder() if tel is not None
                    else None)
        if self._rt is not None:
            # SLO burn counters measure against this server's targets
            self._rt.set_slo(*_slo_seconds(cfg))
        if cfg.controller.enabled:
            # online feedback controller (ISSUE 19): stepped from the
            # worker loop (every knob it turns mutates worker-owned
            # state), reading burn rates / component p99s each interval
            from .controller import ServingController
            self._controller = ServingController(
                cfg.controller,
                chain_depth=self.session.max_depth,
                draft_len=self.session._draft_cfg,
                shed_depth=cfg.shed_queue_depth,
                set_shed_depth=self._set_shed_depth,
                set_chain_depth=self.session.set_chain_depth,
                set_draft_len=self.session.set_draft_len,
                registry=(tel.get_registry() if tel is not None
                          else None))
        # GIL-atomic bool flags shared with the worker: _accepting is
        # flipped off by a dying worker (the losing race costs one
        # submit that then hits the _worker_error check), _stopping is
        # mailbox-ordered (the worker only sets it after reading a stop
        # message this thread posted) — benign by construction
        self._accepting = True      # graftlint: disable=GL052
        self._stopping = False      # graftlint: disable=GL052
        self._thread = threading.Thread(target=self._work, daemon=True,
                                        name="ds-serving-loop")
        self._thread.start()
        log_dist("AsyncInferenceServer: serving loop started "
                 f"(k={self.session.k}, chain depth "
                 f"{self.session.depth}, "
                 f"{'ring' if self.session.ring_mode else 'chain'} mode)")

    async def stop(self, drain: bool = True) -> None:
        """Shut the serving loop down. ``drain=True`` finishes the
        requests already submitted first; ``drain=False`` cancels
        them."""
        if self._thread is None:
            return
        self._accepting = False
        if not drain:
            for h in list(self._handles.values()):
                h.cancel()
        self._post(("stop",))
        await self._aloop.run_in_executor(None, self._thread.join)
        self._thread = None
        if self._worker_error is not None:
            raise self._worker_error

    def _admit_handle(self, max_new_tokens, priority,
                      uid, prompt_tokens: int):
        """Shared submit-side bookkeeping: accept/backpressure checks,
        shed decision, handle + trace registration. Returns
        (handle, max_new, prio, shed) — a shed handle is already
        finished (its stream raises ``RequestFailed`` naming the shed)
        and must NOT be posted to the worker."""
        if not self._accepting:
            raise RuntimeError("server is not accepting requests")
        if self._worker_error is not None:
            raise RuntimeError(
                "serving loop died") from self._worker_error
        cfg = self.config
        if cfg.max_queue and self._open >= cfg.max_queue:
            raise RuntimeError(
                f"serving queue full ({self._open} open requests >= "
                f"max_queue {cfg.max_queue})")
        shed_at = self._shed_depth
        if shed_at and self._open >= shed_at:
            # admission control (ISSUE 19): past the bound the request
            # fails FAST instead of aging in the mailbox (a CPU run
            # before the chip: unbounded admission buried an 11.5 s
            # TTFT p99 under 11.2 s of queue_wait). Counted three ways — handle
            # error, ds_serving_shed_total, reqtrace outcome=shed —
            # never silently dropped.
            uid = next(self._uid) if uid is None else int(uid)
            handle = RequestHandle(uid, self)
            msg = (f"request {uid} shed: {self._open} open requests "
                   f">= admission bound {shed_at}")
            self._shed_count += 1
            tel = _telemetry()
            if self._rt is not None:
                handle.trace_id = self._rt.enqueue(
                    uid, priority=int(
                        priority if priority is not None
                        else cfg.default_priority),
                    prompt_tokens=prompt_tokens)
                self._rt.finished(uid, "shed", error=msg)
            if tel is not None:
                reg = tel.get_registry()
                if reg is not None:
                    reg.counter("ds_serving_shed_total",
                                "requests fast-failed at the admission "
                                "bound").inc()
            handle._push(TokenEvent(uid, [], finished=True, error=msg))
            return handle, None, None, True
        # callers spanning several replicas (the router) pass their own
        # globally-unique uid so one request keeps ONE trace across
        # prefill hand-off, migration and reroute
        uid = next(self._uid) if uid is None else int(uid)
        if uid in self._handles:
            raise RuntimeError(f"request uid {uid} already open")
        handle = RequestHandle(uid, self)
        self._handles[uid] = handle
        self._open += 1
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else cfg.default_max_new_tokens)
        prio = int(priority if priority is not None
                   else cfg.default_priority)
        if self._rt is not None:
            # the trace's enqueue timestamp is the client-visible
            # submit time — mailbox marshalling counts as queue wait
            # (idempotent: a router-owned trace keeps its original id)
            handle.trace_id = self._rt.enqueue(
                uid, priority=prio, prompt_tokens=prompt_tokens,
                max_new_tokens=max_new)
        return handle, max_new, prio, False

    async def submit(self, prompt: Sequence[int], *,
                     max_new_tokens: Optional[int] = None,
                     priority: Optional[int] = None,
                     uid: Optional[int] = None) -> RequestHandle:
        """Queue one generation request; returns its streaming handle.
        Raises when the server is stopped or ``max_queue`` is hit."""
        toks = [int(t) for t in prompt]
        handle, max_new, prio, shed = self._admit_handle(
            max_new_tokens, priority, uid, len(toks))
        if not shed:
            self._post(("submit", handle.uid, toks, max_new, prio))
        return handle

    async def submit_imported(self, state, *,
                              max_new_tokens: Optional[int] = None,
                              priority: Optional[int] = None,
                              uid: Optional[int] = None,
                              emit_carried: bool = False
                              ) -> RequestHandle:
        """Queue a MIGRATED sequence (a ``KVExportState`` from another
        engine's ``export_request``) — the decode half of a
        disaggregated hand-off (ISSUE 13). The KV payload lands in
        this replica's pool at admission, position-exactly; with
        ``emit_carried`` the already-generated tokens re-emit at the
        head of the stream (the router leaves it off — it already
        streamed them during the hand-off)."""
        n_gen = int(state.n_generated)
        n_prompt = len(state.tokens) - n_gen
        if n_prompt <= 0:
            raise ValueError(
                "submit_imported() needs at least one prompt token")
        max_new_chk = int(max_new_tokens if max_new_tokens is not None
                          else self.config.default_max_new_tokens)
        if max_new_chk <= n_gen:
            raise ValueError(
                f"imported request already generated {n_gen} of "
                f"{max_new_chk} tokens — finish it without a hand-off")
        handle, max_new, prio, shed = self._admit_handle(
            max_new_tokens, priority, uid, n_prompt)
        if not shed:
            self._post(("submit_imported", handle.uid, state, max_new,
                        prio, bool(emit_carried)))
        return handle

    async def generate(self, prompt: Sequence[int], **kw) -> list[int]:
        """submit() + collect the full stream."""
        h = await self.submit(prompt, **kw)
        return await h.tokens()

    def kill(self) -> None:
        """Fault injection (ISSUE 17): make the worker thread die at
        its next mailbox drain, exactly as an engine fault would — the
        death path fails every open handle with ``RequestFailed``
        ("serving loop died"), closes their traces, and flips
        ``accepting`` off, so the router's drain-and-reroute (and the
        health detector's silence->suspect->dead arc) is exercised for
        real. The fleet bench and the kill-reroute tests drive this."""
        self._post(("die",))

    def metrics(self) -> dict:
        """Engine serving counters merged with the scheduler's
        (preemptions/restores/cancellations/admitted/chain_drains/
        imports) and the open-request gauge."""
        m = dict(self.engine.serving_metrics())
        if self.session is not None:
            m.update(self.session.counters)
        m["open_requests"] = self._open
        m["shed_requests"] = self._shed_count
        m["replica"] = self.config.replica
        if self._controller is not None:
            m["controller_actions"] = self._controller.action_counts()
            m["controller_chain_depth"] = self._controller.chain_depth
            m["controller_draft_len"] = self._controller.draft_len
            m["controller_shed_depth"] = self._controller.shed_depth
        return m

    def _set_shed_depth(self, depth: int) -> None:
        """Controller knob: move the live admission bound (worker
        thread writes, submit() reads — GIL-atomic int)."""
        self._shed_depth = int(depth)   # graftlint: disable=GL052

    # -- router-facing placement probes (ISSUE 13; all host-only) ------
    @property
    def accepting(self) -> bool:
        """True while submits are admitted (started, not stopping,
        worker alive)."""
        return bool(self._accepting) and self._worker_error is None

    @property
    def open_requests(self) -> int:
        """Queued + running requests (the router's load signal)."""
        return self._open

    @property
    def free_blocks(self) -> int:
        """Schedulable KV headroom of this replica's pool (truly free
        plus evictable prefix-cached blocks; GIL-atomic reads of
        worker-owned accounting — a placement HINT, not a
        reservation)."""
        return self.engine.free_blocks

    def prefix_affinity(self, tokens) -> int:
        """FULL leading blocks of ``tokens`` this replica's prefix
        cache already holds (the hash-chained match from PR 4) — the
        router's placement key. Pure host-side query against
        worker-owned dicts (point ``get`` lookups only, GIL-atomic);
        the match is re-walked under the worker at admission, so a
        stale answer costs placement quality, never correctness."""
        return len(self.engine.state_manager.prefix_match(
            [int(t) for t in tokens]))

    # ------------------------------------------------------------------
    def _post(self, msg: tuple) -> None:
        # O(1) append under the mailbox lock; the worker holds the same
        # lock only for a pointer swap (_drain_mailbox), never around
        # engine/device work — the loop cannot stall on it
        with self._mail_lock:       # graftlint: disable=GL051
            self._mailbox.append(msg)
        self._wake.set()

    def _emit(self, events: list[TokenEvent]) -> None:
        """Worker -> event loop handoff (one call per step). All
        ``_open``/handle mutation happens on the event-loop thread
        (submit() runs there too), so the counter needs no lock."""

        def deliver(evts=list(events)):
            for e in evts:
                h = self._handles.get(e.uid)
                if h is not None:
                    h._push(e)
                if e.finished:
                    self._handles.pop(e.uid, None)
                    self._open -= 1

        self._aloop.call_soon_threadsafe(deliver)

    def _work(self) -> None:    # graftsan: domain=worker
        """Worker thread: owns the session and every engine/JAX call."""
        s = self.session
        cfg = self.config
        aff = getattr(self.engine, "_affinity", None)
        if aff is not None:
            # this thread is now THE engine owner: re-stamp (engine
            # warmup may have auto-bound the constructing thread), and
            # release ownership again on exit so a later closed-loop
            # driver on another thread can re-bind instead of raising
            aff.bind(force=True)
        try:
            while True:
                stop = self._drain_mailbox(s)
                if stop and not s.has_work():
                    break
                if not s.has_work():
                    tel = _telemetry()
                    if tel is not None:
                        # the idle loop is ALIVE: without this beat an
                        # idle replica's silence would read as death
                        self._beat(tel)
                    self._control()
                    self._wake.wait(timeout=0.1)
                    self._wake.clear()
                    continue
                events = s.step()
                self._observe(s)
                self._control()
                if events:
                    self._emit(events)
                elif s.has_work():
                    # waiting on admission headroom (or another engine
                    # user): back off instead of spinning
                    time.sleep(cfg.idle_poll_s)
        except BaseException as e:   # noqa: BLE001 — surfaced on stop()
            self._worker_error = e
            self._accepting = False
            fail = [TokenEvent(uid, [], finished=True,
                               error=f"serving loop died: {e}")
                    for uid in list(self._handles)]
            if fail:
                self._emit(fail)
            if self._rt is not None:
                # close the traces of every request this server still
                # owned — including submits stranded in the mailbox
                # that never reached the loop (finished() is a no-op
                # for uids the loop already closed); otherwise they
                # haunt in_flight()/hang dumps as ever-aging ghosts
                for uid in list(self._handles):
                    self._rt.finished(uid, "failed",
                                      error="serving loop died")
        finally:
            try:
                s.close()
            except Exception:   # noqa: BLE001 — shutdown best-effort
                pass
            if aff is not None:
                aff.unbind()

    def _drain_mailbox(self, s: FusedServeLoop) -> bool:
        with self._mail_lock:
            msgs, self._mailbox = self._mailbox, []
        stop = self._stopping
        for m in msgs:
            if m[0] == "submit":
                _, uid, prompt, max_new, prio = m
                s.submit(prompt, max_new, priority=prio, uid=uid)
            elif m[0] == "submit_imported":
                _, uid, state, max_new, prio, emit = m
                s.submit_imported(state, max_new, priority=prio,
                                  uid=uid, emit_carried=emit)
            elif m[0] == "cancel":
                s.cancel(m[1])
            elif m[0] == "stop":
                stop = self._stopping = True
            elif m[0] == "die":
                raise RuntimeError("fault injection: replica killed")
        return stop

    def _control(self) -> None:     # graftsan: domain=worker
        """One (rate-limited) controller interval. Runs on the worker
        thread — the depth/draft knobs mutate session state the worker
        owns; the shed bound crosses back to submit() GIL-atomically.
        Works with telemetry off too: the signal reader then degrades
        to the open-request fallback, which still protects the
        queue."""
        c = self._controller
        if c is None:
            return
        from .controller import read_server_signals
        tel = _telemetry()
        c.maybe_step(lambda: read_server_signals(self, tel))

    def _observe(self, s: FusedServeLoop) -> None:
        """Per-step telemetry: scheduler counters -> registry, plus a
        flight-recorder heartbeat so a wedged loop leaves forensics."""
        tel = _telemetry()
        if tel is None:
            return
        fr = tel.get_flight_recorder()
        if fr is not None:
            # the heartbeat names the in-flight requests (ISSUE 10):
            # a wedged serving loop's flight-recorder ring and hang
            # dump then say WHICH uids were stuck and for how long,
            # not just that the thread stalled. The full oldest-first
            # summary scans the in-flight map, so refresh it at most
            # ~4 Hz; between refreshes the heartbeat carries the O(1)
            # live count (this loop steps every few ms under load)
            if self._rt is None:
                meta = {"inflight": self._open}
            else:
                now = time.monotonic()
                if now >= self._hb_next:
                    self._hb_meta = self._rt.heartbeat_meta()
                    self._hb_next = now + 0.25
                meta = {**self._hb_meta,
                        "inflight": self._rt.inflight_count()}
            if cfg_replica := self.config.replica:
                # fleet runs (ISSUE 17): the hang dump's progress ring
                # then names WHICH replica's loop stalled
                meta["replica"] = cfg_replica
            fr.progress("serving_loop", **meta)
        reg = tel.get_registry()
        if reg is None:
            return
        for key in LOOP_COUNTER_KEYS:
            reg.counter(f"ds_serving_{key}_total",
                        f"serving scheduler counter {key}").set_total(
                s.counters[key], engine="v2")
        reg.gauge("ds_serving_open_requests",
                  "requests open on the async server "
                  "(queued + running)").set(self._open, engine="v2")
        self._beat(tel)

    def _beat(self, tel) -> None:
        """Fleet-health heartbeat (ISSUE 17): liveness of THIS loop
        thread, sent from the busy and idle paths alike — deliberately
        a SEPARATE channel from ``fr.progress()``, which means "work
        advanced" and stays silent while idle (the hang watchdog's
        contract). At a ~4 Hz cadence it also samples the time-series
        ring and feeds the composite-score inputs (queue saturation,
        KV headroom, windowed SLO burn, sanitizer violations, stall
        age) to the monitor."""
        hm = tel.get_health_monitor()
        if hm is None:
            return
        name = self.config.replica or "replica0"
        now = time.monotonic()
        # rate-limit the forwarded beats: a busy tick loop calls
        # _beat per tick, and a burst of sub-ms beats would both
        # shrink the detector's empirical mean and flush the real
        # cadence out of its bounded window
        if now >= self._beat_next:
            self._beat_next = now + max(hm.min_interval_s, 1e-3)
            hm.heartbeat(name)
        if now < self._health_next:
            return
        self._health_next = now + 0.25
        reg = tel.get_registry()
        ts = tel.get_timeseries()
        burn = viol = None
        if ts is not None:
            ts.maybe_sample(reg)
            # both breach counters under one stem; fastest window =
            # the detector's reaction signal
            burn = ts.burn_rate("ds_serving_slo_",
                                "ds_serving_requests_total",
                                tel.burn_windows()[0])
            latest = ts.latest()
            if latest is not None:
                viol = int(sum(
                    v for k, v in latest[1].items()
                    if "ds_blocksan_violations" in k
                    or "ds_meshsan_violations" in k))
        fr = tel.get_flight_recorder()
        cfg = self.config
        hm.observe(
            name,
            queue_frac=(self._open / cfg.max_queue
                        if cfg.max_queue else None),
            free_blocks=self.engine.free_blocks,
            slo_burn=burn, violations=viol,
            stalled_s=fr.stalled_for() if fr is not None else None)
