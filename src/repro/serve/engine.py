"""Request lifecycle manager: submit / step / harvest.

The engine fronts a ContinuousBatcher with a FIFO admission queue and
per-request stop conditions (max_new_tokens, optional EOS token, KV
capacity).  One ``step()`` = admit as many queued requests as there are
free slots (each costs one fixed-shape prefill + seat), then one
batched decode step for every lane; finished requests evict their slot
immediately, so a queued request can join on the very next step —
continuous batching, not static batching.

With ``packed=True`` the engine serves from an element-mode
PackedParamStore: decode matmuls consume compact (vals, idx) tensors
through kernels/nm_spmm at ~N/M of the dense weight HBM bytes
(``engine.hbm_report()`` gives the actual numbers).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional

from repro.core.sparsity import DENSE, SparsityConfig
from repro.serve.batcher import ContinuousBatcher
from repro.serve.cache_store import CacheStore, Lane, prefix_chain
from repro.serve.packed_params import PackedParamStore


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static engine shape — fixes the one-and-only compiled step."""

    n_slots: int = 4          # concurrent requests (KV lanes)
    max_len: int = 96         # per-slot KV depth (prompt + generation)
    prompt_bucket: int = 32   # prompts right-padded to this length
    eos_token: Optional[int] = None  # engine-wide default stop token
    packed: bool = False      # serve from element-packed N:M weights
    idx_bits: Optional[int] = None   # stored index width for the packed
    # store: 4 (u4, two offsets/byte), 8 (byte-wide), or None to pick
    # automatically (u4 whenever M <= 16 — packed_params.default_idx_bits)
    prefix_cache: int = 0     # lanes pooled for prefix/KV reuse (0 = off):
    # an admission whose prompt-bucket hash chain matches a pooled lane
    # seats that lane instead of prefilling (serve/cache_store.py)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos: Optional[int]
    state: str = "queued"             # queued | running | done
    slot: Optional[int] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    submit_step: int = 0
    finish_step: int = 0

    @property
    def finish_reason(self) -> str:
        if self.eos is not None and self.tokens and self.tokens[-1] == self.eos:
            return "eos"
        return "length"


class ServeEngine:
    """Continuous-batching greedy-decode engine over N:M-sparse weights."""

    def __init__(self, params, cfg, sp_cfg: SparsityConfig = DENSE,
                 serve_cfg: Optional[ServeConfig] = None, *, mesh=None,
                 cache_dtype=None):
        import jax.numpy as jnp

        serve_cfg = serve_cfg if serve_cfg is not None else ServeConfig()
        self.cfg = cfg
        self.sp_cfg = sp_cfg
        self.serve_cfg = serve_cfg
        self.mesh = mesh
        self.store: Optional[PackedParamStore] = None
        if serve_cfg.packed:
            self.store = PackedParamStore.pack(params, sp_cfg,
                                               idx_bits=serve_cfg.idx_bits)
            params = self.store.params
        shardings = None
        if mesh is not None:
            # SPMD serving: resolve SERVE_BATCH-rule shardings (weights
            # TP over "model" with N:M groups unsplit, slot lanes over
            # the DP axes) and pin the engine's residents to them — on
            # a one-device mesh this is what places a fleet replica on
            # its own chip.
            from repro.launch import spmd
            shardings = spmd.serve_shardings(
                cfg, mesh, sp_cfg, n_slots=serve_cfg.n_slots,
                max_len=serve_cfg.max_len, packed=serve_cfg.packed,
                idx_bits=serve_cfg.idx_bits,
                cache_dtype=cache_dtype or jnp.bfloat16)
        self.batcher = ContinuousBatcher(
            params, cfg, sp_cfg,
            n_slots=serve_cfg.n_slots, max_len=serve_cfg.max_len,
            prompt_bucket=serve_cfg.prompt_bucket,
            cache_dtype=cache_dtype or jnp.bfloat16, mesh=mesh,
            shardings=shardings)
        self._queue: deque[Request] = deque()
        self._lane_queue: deque = deque()        # (Request, Lane) handoffs
        self._running: Dict[int, Request] = {}   # slot -> request
        self._done: Dict[int, Request] = {}      # rid -> request
        self._next_rid = 0
        self.step_count = 0
        self.decode_steps = 0
        self.decoded_tokens = 0   # harvested from active lanes only
        self.prefix_pool: Optional[CacheStore] = (
            CacheStore(serve_cfg.prefix_cache)
            if serve_cfg.prefix_cache > 0 else None)

    # -- lifecycle ----------------------------------------------------------

    def validate(self, prompt, max_new_tokens: int) -> List[int]:
        """Check a request against the static engine shape: the prompt
        must fit the prefill bucket and prompt+generation must fit a KV
        lane.  Returns the normalized prompt (fleet frontends call this
        at their own submit time so a bad request fails at the caller,
        not inside a later fleet step)."""
        prompt = [int(t) for t in prompt]
        sc = self.serve_cfg
        if not 0 < len(prompt) <= sc.prompt_bucket:
            raise ValueError(f"prompt length {len(prompt)} not in "
                             f"(0, {sc.prompt_bucket}]")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > sc.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds per-slot KV capacity {sc.max_len}")
        return prompt

    def submit(self, prompt, max_new_tokens: int = 16,
               eos: Optional[int] = None) -> int:
        """Queue a request; returns its rid.  Admission happens in step()."""
        prompt = self.validate(prompt, max_new_tokens)
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      eos=eos if eos is not None else self.serve_cfg.eos_token,
                      submit_step=self.step_count)
        self._queue.append(req)
        return rid

    def submit_lane(self, lane: Lane, max_new_tokens: int = 16,
                    eos: Optional[int] = None, *, prompt=(),
                    tokens=None) -> int:
        """Queue an already-prefilled lane (the decode half of
        prefill/decode disaggregation): the lane's KV is seated into a
        free slot at the next step() — no prefill here, ever.

        ``tokens`` are the tokens already generated for this request
        upstream (at least the prefill's first token); they count
        against ``max_new_tokens``.
        """
        tokens = [int(t) for t in (tokens if tokens is not None
                                   else [lane.next_token])]
        if not tokens:
            raise ValueError("a handed-off lane carries >= 1 token")
        if max_new_tokens < len(tokens):
            raise ValueError(f"lane already holds {len(tokens)} tokens, "
                             f"max_new_tokens={max_new_tokens}")
        if lane.pos + (max_new_tokens - len(tokens)) + 1 > self.serve_cfg.max_len:
            raise ValueError(
                f"lane pos ({lane.pos}) + remaining tokens exceeds "
                f"per-slot KV capacity {self.serve_cfg.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=[int(t) for t in prompt],
                      max_new_tokens=max_new_tokens,
                      eos=eos if eos is not None else self.serve_cfg.eos_token,
                      submit_step=self.step_count, tokens=tokens)
        self._lane_queue.append((req, lane))
        return rid

    def _should_stop(self, req: Request) -> bool:
        if len(req.tokens) >= req.max_new_tokens:
            return True
        if req.eos is not None and req.tokens and req.tokens[-1] == req.eos:
            return True
        return False

    def _finish(self, req: Request) -> None:
        req.state = "done"
        req.finish_step = self.step_count
        self.batcher.evict(req.slot)
        del self._running[req.slot]
        self._done[req.rid] = req

    def step(self) -> dict:
        """Admit from the queue, decode one token for every active slot.

        Returns an event dict: {"admitted": [rid], "finished": [rid],
        "active": n_running_after}.
        """
        events = {"admitted": [], "finished": [], "active": 0}
        # 1a. lane admission first: handed-off lanes already paid their
        # prefill upstream — seat them before spending prefills here
        while self._lane_queue and self.batcher.kv.n_free > 0:
            req, lane = self._lane_queue.popleft()
            req.slot = self.batcher.seat_lane(lane)
            req.state = "running"
            self._running[req.slot] = req
            events["admitted"].append(req.rid)
            if self._should_stop(req):
                self._finish(req)
                events["finished"].append(req.rid)
        # 1b. admission: queued requests join mid-flight into free slots
        # (a prefix-pool hit seats the pooled lane and skips the prefill)
        while self._queue and self.batcher.kv.n_free > 0:
            req = self._queue.popleft()
            lane = None
            if self.prefix_pool is not None:
                chain = prefix_chain(req.prompt,
                                     self.serve_cfg.prompt_bucket)
                lane = self.prefix_pool.get(chain)
                if lane is None:
                    lane = self.batcher.prefill(req.prompt, key=chain)
                    self.prefix_pool.put(lane)
            else:
                lane = self.batcher.prefill(req.prompt)
            req.slot = self.batcher.seat_lane(lane)
            req.state = "running"
            req.tokens.append(lane.next_token)
            self._running[req.slot] = req
            self.decoded_tokens += 1
            events["admitted"].append(req.rid)
            if self._should_stop(req):   # e.g. max_new_tokens == 1
                self._finish(req)
                events["finished"].append(req.rid)
        # 2. one batched decode step (all lanes; free lanes are garbage)
        if self._running:
            nxt = self.batcher.step()
            self.decode_steps += 1
            for slot, req in list(self._running.items()):
                tok = int(nxt[slot])
                req.tokens.append(tok)
                self.decoded_tokens += 1
                if self._should_stop(req):
                    self._finish(req)
                    events["finished"].append(req.rid)
        events["active"] = len(self._running)
        self.step_count += 1
        return events

    def reset(self) -> None:
        """Clear host-side counters/results between workloads while
        keeping the expensive state (packed store, compiled prefill/
        seat/decode, device cache, prefix pool) — stale KV lanes are
        harmless by the slot-reuse invariant.  Refuses with work in
        flight."""
        if self._queue or self._lane_queue or self._running:
            raise RuntimeError("reset() with requests queued or running")
        self._done = {}
        self.step_count = 0
        self.decode_steps = 0
        self.decoded_tokens = 0
        self.batcher.prefill_calls = 0

    def run(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Drive step() until queue and slots drain; returns harvest()."""
        steps = 0
        while ((self._queue or self._lane_queue or self._running)
               and steps < max_steps):
            self.step()
            steps += 1
        if self._queue or self._lane_queue or self._running:
            raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return self.harvest()

    @property
    def finished_requests(self) -> List[Request]:
        """Finished Request objects (submit/finish step stamps intact);
        does not pop — harvest() does."""
        return list(self._done.values())

    def harvest(self) -> Dict[int, List[int]]:
        """Pop finished requests: {rid: generated token ids}."""
        out = {rid: req.tokens for rid, req in self._done.items()}
        self._done = {}
        return out

    # -- fleet hooks --------------------------------------------------------

    def prefill_to_lane(self, prompt, max_new_tokens: int = 16) -> Lane:
        """Dedicated-prefill-engine entry point: run prefill (or hit the
        prefix pool) and return the seatable Lane WITHOUT occupying one
        of this engine's slots — the fleet hands the lane to a decode
        engine through a CacheStore."""
        prompt = self.validate(prompt, max_new_tokens)
        chain = prefix_chain(prompt, self.serve_cfg.prompt_bucket)
        if self.prefix_pool is not None:
            lane = self.prefix_pool.get(chain)
            if lane is not None:
                return lane
        lane = self.batcher.prefill(prompt, key=chain)
        if self.prefix_pool is not None:
            self.prefix_pool.put(lane)
        return lane

    def export_lane(self, rid: int) -> Lane:
        """Freeze a RUNNING request's live KV lane into a batch-1 Lane
        (cache slice + next token + position) and release its slot; the
        request is detached from this engine.  Seating the lane on
        another engine (``submit_lane``) continues the token stream
        bitwise-identically."""
        req = next((r for r in self._running.values() if r.rid == rid),
                   None)
        if req is None:
            raise KeyError(f"rid {rid} is not running on this engine")
        lane = self.batcher.export_lane(req.slot)
        self.batcher.evict(req.slot)
        del self._running[req.slot]
        req.slot, req.state = None, "exported"
        return lane

    def prefix_match_depth(self, chain) -> int:
        """How many leading prompt blocks of ``chain`` this engine's
        prefix pool already holds — the router's KV-affinity signal."""
        return (self.prefix_pool.match_depth(chain)
                if self.prefix_pool is not None else 0)

    def utilization(self) -> dict:
        """Live occupancy snapshot the fleet scheduler routes on."""
        n = self.serve_cfg.n_slots
        queued = len(self._queue) + len(self._lane_queue)
        return {"n_slots": n, "running": len(self._running),
                "queued": queued, "free_slots": self.batcher.kv.n_free,
                "load": (len(self._running) + queued) / n}

    # -- introspection ------------------------------------------------------

    @property
    def n_queued(self) -> int:
        return len(self._queue) + len(self._lane_queue)

    @property
    def n_running(self) -> int:
        return len(self._running)

    @property
    def prefill_steps(self) -> int:
        """Compiled-prefill invocations since construction/reset —
        prefix-pool hits make this smaller than the admission count."""
        return self.batcher.prefill_calls

    def hbm_report(self) -> Optional[dict]:
        """Actual packed-weight HBM bytes (None when serving dense)."""
        return self.store.report() if self.store is not None else None

    def stats(self) -> dict:
        out = {
            "steps": self.step_count,
            "decode_steps": self.decode_steps,
            "decoded_tokens": self.decoded_tokens,
            "prefill_steps": self.prefill_steps,
            "n_slots": self.serve_cfg.n_slots,
            "queued": self.n_queued,
            "running": self.n_running,
        }
        if self.prefix_pool is not None:
            out["prefix_pool"] = self.prefix_pool.stats()
        return out
