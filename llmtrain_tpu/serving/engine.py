"""Paged decode engine: bucketed jitted prefill/decode over the block pool.

The execution layer of the continuous-batching server (scheduler.py owns
WHEN sequences join/leave; this module owns HOW a step runs):

* **Two programs, shape-bucketed.** ``prefill`` runs one joining
  sequence's prompt (padded to a prompt-length bucket) through the paged
  model, writing its K/V blocks and sampling its first token; ``decode``
  advances every in-flight sequence one token (batch padded to a
  batch-size bucket). XLA compiles once per bucket, so the total compile
  count is bounded by ``len(prompt_buckets) + len(batch_buckets)`` — a
  budget :meth:`compile_stats` exposes and tests assert
  (tests/test_serving_engine.py), because unbounded recompilation is the
  classic way a JAX server falls over in production.
* **Per-row sampling with per-request seeds.** Greedy rows take the raw
  argmax; sampled rows replay ``generate()``'s exact recipe —
  temperature scale, top-k/top-p filter (same thresholds as
  ``generation.filter_logits``), then ``categorical(fold_in(key(seed),
  emit_index))`` — per ROW, so a batched decode emits the same tokens the
  single-request path would (the exactness contract the acceptance test
  pins under greedy decoding).
* **Shared pool cache.** The paged cache is batch-shape-independent
  (models/gpt.py ``_paged_decode_attention``), so every bucket's program
  reads/writes the SAME donated cache buffers — join/evict never copies
  K/V.
* **Every call says where its host time went.** With a ``span_factory``
  (the scheduler's) each call records
  ``serve/engine.stage`` (numpy columns, tables, ``jnp.asarray``),
  ``serve/engine.dispatch`` (the jitted call until it returns) and
  ``serve/engine.fetch`` (the blocking read of the result), with ``call``
  naming the program. The ``stage`` span also carries what the call is
  about to waste, counted where the padding happens: ``prompt_tokens`` and
  ``bucket`` of a prefill, ``kv_live_tokens`` and ``kv_gathered_tokens``
  of a decode, and for a model that declares it (``paged_kv_form``: GPT,
  Llama, Falcon-H1) ``kv_form``, the form of the paged read the decode
  program runs (``"rows"`` or ``"heads"``, models/gpt.py). Without a
  factory nothing is recorded.
* **Two kinds of cache leaf, told apart in the tree.** A model with a
  recurrent mixer (models/falcon_h1.py) keeps, beside the block pool,
  leaves whose leading axis is a sequence's *state row* (their variable
  names begin with ``state_``; serving/paged_kv.py owns the rows). When
  the cache tree holds such leaves, ``prefill`` and ``decode`` also stage
  each row's state row id (and ``prefill`` hands the model ``true_len``),
  the ``stage`` span also carries ``state_rows`` / ``state_bytes`` (decode)
  and ``state_bytes`` / ``scan_chunks`` (prefill), the COW copy leaves
  those leaves alone, and the prefix cache and ``verify`` are refused by
  name. When it holds none, nothing of this is staged, compiled or counted.
* **A pool leaf is whatever the model pages.** Per-head K and V
  (``paged_key`` / ``paged_value``), one latent row a position
  (``paged_latent``, models/latent_moe.py) or an index key beside K and V
  (``paged_index``, models/indexed_moe.py): the engine indexes a pool
  leaf's leading (block) axis only, and ``kv_live_tokens`` /
  ``kv_gathered_tokens`` count positions, whatever a position holds.
* **What the expert layers of a decode call did.** A model that says it
  has expert layers (``expert_layers``; models/moe.py:DroplessMoE sows
  ``moe_stats``) gets two int32 appended to the decode program's sampled
  tokens, so they come off the device in the tokens' own read-back, and
  the call's ``serve/engine.fetch`` span carries them: ``expert_pairs``
  (token-expert pairs routed to experts held here, summed over the call's
  expert layers and over every row of the call's bucket) and
  ``experts_hit`` (held experts that got at least one pair, summed over
  layers). They are on ``fetch`` and not on ``stage`` because they exist
  only once the program has run. A model without expert layers compiles
  and reads back exactly what it did before.
* **What a selection scored and attended.** A model whose queries attend
  a chosen subset of the cache says how many positions at most
  (``selects_positions``; models/indexed_moe.py). The ``stage`` span of its
  calls then also carries, computed on the host from positions alone: of a
  decode ``kv_selected_tokens`` (sum over rows of ``min(live, topk)``: the
  positions whose K/V the call must read, where ``kv_live_tokens`` are those
  it must score) and ``rows_past_topk`` (rows that select at all); of a
  prefill ``index_pairs`` (sum over the slab's positions ``p`` of ``p + 1``:
  (query, position) pairs scored) and ``selected_pairs`` (sum of ``min(p +
  1, topk)``: pairs attended). Other models' spans are what they were.
* **A second pool for window layers.** A model whose window layers need no
  block that fell out of the window says so in ONE attribute,
  ``paged_window`` (the window in positions; models/windowed_moe.py). The
  engine then sizes a second pool of ``1 + slots * ring`` blocks (``ring =
  ceil(window / block_tokens) + 1``; serving/paged_kv.py "window blocks"),
  asks the model for leaves of that size (``for_paged_decoding(
  window_num_blocks=)``; the window layers' leaves must NOT have the global
  pool's block count), stages each row's ring table beside its block table
  (``window_tables``) and hands prefill ``true_len`` (the model then returns
  the last true position's logits alone). The ``stage`` span also carries,
  from positions alone: of a decode ``kv_window_tokens`` (sum over rows of
  ``min(live, window)``: what ONE window layer must read, beside
  ``kv_live_tokens`` for one global layer), ``kv_window_gathered_tokens``
  (batch bucket x ring x ``block_tokens``: what one window layer gathers,
  beside ``kv_gathered_tokens`` for a global one) and ``window_blocks_bound`` /
  ``global_blocks_bound`` (the pool's own accounting at that tick); of a
  prefill ``window_pairs`` (sum over the true prompt's positions ``p`` of
  ``min(p + 1, window)``) and ``causal_pairs`` (sum of ``p + 1``). What
  would read a window layer's earlier keys through the tables is refused by
  name: the prefix cache (and with it the COW copy), chunked prefill and
  ``verify``. Other models' cache trees, programs and spans are what they
  were.
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.timeline import process_span
from ..utils.logging import get_logger
from .paged_kv import PagedKVPool, window_ring_blocks

logger = get_logger()


def _round_up_buckets(limit: int, *, start: int = 1) -> list[int]:
    """Powers of two up to (and always including) ``limit``."""
    buckets: list[int] = []
    b = start
    while b < limit:
        buckets.append(b)
        b *= 2
    buckets.append(limit)
    return buckets


def bucket_for(n: int, buckets: list[int]) -> int:
    """Smallest bucket >= n; raises when n exceeds every bucket."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest bucket ({buckets[-1]})")


def _filter_rows(
    scaled: jax.Array, top_ks: jax.Array, top_ps: jax.Array
) -> jax.Array:
    """Per-row top-k / top-p masking with DYNAMIC knobs.

    Same thresholds as ``generation.filter_logits`` (kth-largest value;
    exclusive-cumulative-mass nucleus cut) but per row and data-dependent,
    so one compiled program serves every sampling configuration —
    per-request knobs must not multiply the compile count. ``top_ks <= 0``
    and ``top_ps`` outside (0, 1) disable the respective filter, matching
    generate()'s out-of-band conventions.
    """
    v = scaled.shape[-1]
    # top-k: threshold at each row's k-th largest (k clamped into [1, V]).
    desc = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)  # (B, V)
    k_idx = jnp.clip(top_ks - 1, 0, v - 1)
    kth = jnp.take_along_axis(desc, k_idx[:, None], axis=-1)  # (B, 1)
    kth = jnp.where((top_ks > 0)[:, None], kth, -jnp.inf)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    # top-p composes AFTER top-k, on the masked logits (filter_logits
    # order): keep the smallest descending-prob prefix whose EXCLUSIVE
    # cumulative mass is < p (always keeps the argmax).
    desc = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
    probs = jax.nn.softmax(desc, axis=-1)
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    keep = exclusive < top_ps[:, None]
    thr = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
    active = ((top_ps > 0.0) & (top_ps < 1.0))[:, None]
    return jnp.where(active & (scaled < thr), -jnp.inf, scaled)


def _sample_rows(
    logits: jax.Array,  # (B, V) f32
    seeds: jax.Array,  # (B,) uint32 — per-request rng seed
    emit_idx: jax.Array,  # (B,) int32 — tokens already emitted by the row
    temps: jax.Array,  # (B,) f32; 0 = greedy
    top_ks: jax.Array,  # (B,) int32; <=0 disables
    top_ps: jax.Array,  # (B,) f32; outside (0,1) disables
) -> jax.Array:
    """One sampling decision per row, generate()-exact per request.

    Greedy rows bypass the filter entirely (raw argmax — _sample_next's
    temperature==0 short-circuit); sampled rows draw
    ``categorical(fold_in(key(seed), emit_idx), filtered)`` — the same
    key schedule generate() uses for a batch of one.
    """
    greedy_tok = jnp.argmax(logits, axis=-1)
    safe_t = jnp.where(temps > 0.0, temps, 1.0)
    scaled = _filter_rows(logits / safe_t[:, None], top_ks, top_ps)

    def one(seed: jax.Array, i: jax.Array, row: jax.Array) -> jax.Array:
        key = jax.random.fold_in(jax.random.key(seed), i)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(one)(seeds, emit_idx, scaled)
    return jnp.where(temps == 0.0, greedy_tok, sampled).astype(jnp.int32)


EXPERT_COUNTERS = ("expert_pairs", "experts_hit")  # what models/moe.py:DroplessMoE sows as `counts`


def _prefill_impl(
    model: Any,
    params: Any,
    cache: Any,
    prompt: jax.Array,  # (1, Tb) padded
    true_len: jax.Array,  # (1,) int32
    offsets: jax.Array,  # (1,) int32 — absolute position of prompt[0]
    block_tables: jax.Array,  # (1, MB) int32
    seeds: jax.Array,
    temps: jax.Array,
    top_ks: jax.Array,
    top_ps: jax.Array,
    state_rows: jax.Array | None = None,  # (1,) int32, models with state leaves
    window_tables: jax.Array | None = None,  # (1, ring) int32, models with window layers
) -> tuple[Any, jax.Array]:
    # `offsets` starts the row mid-sequence: 0 for a whole prompt, the
    # reused-prefix length under shared-prefix reuse, the chunk start
    # under chunked prefill. The suffix attends earlier positions through
    # the block table (cached K/V), exactly like a multi-token decode.
    # A recurrent state has to be told where the padding starts too.
    state = {} if state_rows is None else {"state_rows": state_rows, "true_len": true_len}
    if window_tables is not None:
        # A window layer must not write the padding: it has to know where it starts.
        state.update(window_tables=window_tables, true_len=true_len)
    logits, mutated = model.apply(
        {"params": params, "cache": cache},
        prompt,
        deterministic=True,
        positions=offsets,
        block_tables=block_tables,
        mutable=["cache"],
        **state,
    )
    # Sample at the LAST REAL position; padded positions' K/V landed in
    # the null block and padded-row logits are garbage nobody reads. A model
    # with window layers was handed `true_len` above and returns that
    # position's logits alone, (1, 1, vocab).
    if window_tables is not None:
        last = logits[:, 0].astype(jnp.float32)
    else:
        last = jnp.take_along_axis(
            logits.astype(jnp.float32), (true_len - 1)[:, None, None], axis=1
        )[:, 0]
    tok = _sample_rows(
        last, seeds, jnp.zeros_like(true_len), temps, top_ks, top_ps
    )
    return mutated["cache"], tok


def _decode_impl(
    model: Any,
    params: Any,
    cache: Any,
    tokens: jax.Array,  # (B,) int32 — each row's last emitted token
    positions: jax.Array,  # (B,) int32 — that token's absolute position
    block_tables: jax.Array,  # (B, MB) int32
    seeds: jax.Array,
    emit_idx: jax.Array,
    temps: jax.Array,
    top_ks: jax.Array,
    top_ps: jax.Array,
    state_rows: jax.Array | None = None,  # (B,) int32, models with state leaves
    window_tables: jax.Array | None = None,  # (B, ring) int32, models with window layers
) -> tuple[Any, jax.Array]:
    state = {} if state_rows is None else {"state_rows": state_rows}
    if window_tables is not None:
        state["window_tables"] = window_tables
    counts_experts = bool(getattr(model, "expert_layers", 0))
    logits, mutated = model.apply(
        {"params": params, "cache": cache},
        tokens[:, None],
        deterministic=True,
        positions=positions,
        block_tables=block_tables,
        mutable=["cache", "moe_stats"] if counts_experts else ["cache"],
        **state,
    )
    tok = _sample_rows(
        logits[:, -1].astype(jnp.float32), seeds, emit_idx, temps, top_ks, top_ps
    )
    if counts_experts:
        # (B + 2,): the tokens, then EXPERT_COUNTERS over the call's expert
        # layers, in the one array the host reads back.
        tok = jnp.concatenate([tok, sum(jax.tree.leaves(mutated["moe_stats"]))])
    return mutated["cache"], tok


def _verify_impl(
    model: Any,
    params: Any,
    cache: Any,
    tokens: jax.Array,  # (B, t) int32 — context token + t-1 draft tokens
    positions: jax.Array,  # (B,) int32 — absolute position of tokens[:, 0]
    block_tables: jax.Array,  # (B, MB) int32
) -> tuple[Any, jax.Array]:
    """Score a (gamma+1)-token slab per row in ONE call: the batched twin
    of speculative.py's target forward. Returns the greedy (argmax) token
    at every slab position — position j's argmax is the target model's
    next token GIVEN drafts < j, which is all greedy acceptance needs
    (speculative.py: accept while draft == argmax, emit the first
    correction from the same logits)."""
    logits, mutated = model.apply(
        {"params": params, "cache": cache},
        tokens,
        deterministic=True,
        positions=positions,
        block_tables=block_tables,
        mutable=["cache"],
    )
    return mutated["cache"], jnp.argmax(
        logits.astype(jnp.float32), axis=-1
    ).astype(jnp.int32)


def is_state_leaf(path: tuple) -> bool:
    """Whether a cache leaf is indexed by state row, not by pool block:
    its variable's name (the last key of its path) begins with ``state_``."""
    return str(getattr(path[-1], "key", "")).startswith("state_")


def _cow_impl(cache: Any, src: jax.Array, dst: jax.Array) -> Any:
    """Copy-on-write device copy: pool block ``src`` → ``dst`` across
    every paged cache leaf. Only the leading (block) axis is indexed, so
    the copy holds for any leaf shape; leaves are ``(num_blocks, bt //
    fold, fold * kv_heads * head_dim)`` with a lane-dense minor dimension
    (``_paged_decode_attention``), in which a block is contiguous and the
    donated pool is updated in place. A state leaf's leading axis is no
    block index: it passes through untouched."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if is_state_leaf(path) else leaf.at[dst].set(leaf[src]),
        cache,
    )


class PagedDecodeEngine:
    """Bucketed paged-KV decode over one model + params.

    Owns the device cache (donated through every step), the host-side
    pool allocator, the bucket policy, and the compile accounting. The
    scheduler calls :meth:`prefill` / :meth:`decode`; nothing here
    decides admission.
    """

    @process_span("startup/build", kind="engine")
    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        block_tokens: int = 16,
        num_blocks: int | None = None,
        max_batch_slots: int = 8,
        prompt_buckets: list[int] | None = None,
        batch_buckets: list[int] | None = None,
        prefix_cache: bool = False,
        prefill_chunk: int = 0,
    ) -> None:
        if not hasattr(model, "for_paged_decoding"):
            raise ValueError(
                "paged serving needs a model exposing for_paged_decoding(); "
                f"{type(model).__name__} does not"
            )
        self.model = model
        self.params = params
        self.block_size = int(model.block_size)
        self.block_tokens = int(block_tokens)
        self.max_blocks_per_seq = -(-self.block_size // self.block_tokens)
        if num_blocks is None:
            # Default: every slot can host a worst-case sequence, + null.
            num_blocks = 1 + max_batch_slots * self.max_blocks_per_seq
        self.max_batch_slots = int(max_batch_slots)
        self.prompt_buckets = sorted(
            prompt_buckets or _round_up_buckets(self.block_size, start=8)
        )
        self.batch_buckets = sorted(
            batch_buckets or _round_up_buckets(self.max_batch_slots)
        )
        if self.prompt_buckets[-1] > self.block_size:
            raise ValueError(
                f"largest prompt bucket ({self.prompt_buckets[-1]}) exceeds "
                f"the model block_size ({self.block_size})"
            )
        if self.batch_buckets[-1] != self.max_batch_slots:
            raise ValueError(
                f"largest batch bucket ({self.batch_buckets[-1]}) must equal "
                f"max_batch_slots ({self.max_batch_slots})"
            )
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0 (0 = off), got {prefill_chunk}"
            )
        if self.prefill_chunk > self.prompt_buckets[-1]:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) exceeds the largest "
                f"prompt bucket ({self.prompt_buckets[-1]}) — chunks must "
                "pad into an existing bucket (the bounded-compile contract)"
            )
        # Window layers (docstring: a second pool): every slot can hold a
        # full ring, + null. 0 everywhere for a model without them.
        self.window_tokens = int(getattr(model, "paged_window", 0))
        self.window_ring = window_ring_blocks(self.window_tokens, self.block_tokens)
        window_num_blocks = 1 + self.max_batch_slots * self.window_ring if self.window_tokens else 0
        if self.window_tokens and self.prefill_chunk:
            raise ValueError(
                "chunked prefill cannot serve a model with window layers: a later "
                "chunk would read earlier keys through the window table, and a "
                "slab attends only its own keys (models/windowed_moe.py)"
            )
        window = {"window_num_blocks": window_num_blocks} if self.window_tokens else {}
        # One state row a slot plus the null row 0, offered to every model;
        # only one with a recurrent state declares leaves that use them.
        self.decode_model = model.for_paged_decoding(
            num_blocks=num_blocks,
            block_tokens=self.block_tokens,
            state_rows=1 + self.max_batch_slots,
            **window,
        )

        # Zero cache pytree from an eval_shape trace — no param init work
        # (the generation.py idiom). Cache shapes are batch-INDEPENDENT
        # (the pool is shared), so one cache serves every bucket.
        mb = self.max_blocks_per_seq
        var_shapes = jax.eval_shape(
            lambda: self.decode_model.init(
                jax.random.key(0),
                jnp.zeros((1, 1), jnp.int32),
                deterministic=True,
                positions=jnp.zeros((1,), jnp.int32),
                block_tables=jnp.zeros((1, mb), jnp.int32),
                **(
                    {"window_tables": jnp.zeros((1, self.window_ring), jnp.int32)}
                    if self.window_tokens else {}
                ),
            )
        )
        self._cache_struct = var_shapes["cache"]
        state_leaves = [
            leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(self._cache_struct)
            if is_state_leaf(path)
        ]
        for leaf in state_leaves:
            if leaf.shape[0] != 1 + self.max_batch_slots:
                raise ValueError(
                    f"state leaf of shape {leaf.shape} does not hold one row a "
                    f"slot plus the null row ({1 + self.max_batch_slots})"
                )
        # Bytes of recurrent state one sequence owns over all layers;
        # 0 = the model has no state leaves and nothing below stages any.
        self.state_bytes_per_row = sum(
            math.prod(leaf.shape[1:]) * leaf.dtype.itemsize for leaf in state_leaves
        )
        self._state_leaves = len(state_leaves)
        if self.window_tokens:
            # Every pool leaf is sized by one of the two pools, and the
            # window layers' by the window pool's.
            blocks = sorted(
                leaf.shape[0]
                for path, leaf in jax.tree_util.tree_leaves_with_path(self._cache_struct)
                if not is_state_leaf(path)
            )
            if set(blocks) != {num_blocks, window_num_blocks}:
                raise ValueError(
                    f"a model with window layers must hold leaves of the global pool ({num_blocks} "
                    f"blocks) and of the window pool ({window_num_blocks}); its leaves lead with {blocks}"
                )
        self._scan_chunk = int(getattr(self.decode_model, "state_scan_chunk", 0))
        self._counts_experts = bool(getattr(self.decode_model, "expert_layers", 0))
        self._selects = int(getattr(self.decode_model, "selects_positions", 0))
        # The form of the paged read a decode call (one token a row) runs;
        # None for a model whose attention is its own (no ``kv_form`` on its spans).
        declared = getattr(self.decode_model, "paged_kv_form", None)
        self._kv_form = declared(t=1) if declared is not None else None
        # Raises by name on prefix_cache with state rows (paged_kv.py).
        self.pool = PagedKVPool(
            num_blocks,
            self.block_tokens,
            prefix_cache=prefix_cache,
            state_rows=self.max_batch_slots if state_leaves else 0,
            window_tokens=self.window_tokens,
            window_num_blocks=window_num_blocks,
        )
        with process_span("startup/pool", num_blocks=int(num_blocks)) as counted:
            self._cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), self._cache_struct
            )
            counted["bytes"] = sum(
                math.prod(s.shape) * s.dtype.itemsize for s in jax.tree.leaves(self._cache_struct)
            )
        # Bumped whenever a failed step forces a cache rebuild: the
        # scheduler compares epochs to learn that in-flight KV was lost.
        self.cache_epoch = 0

        # Per-engine CLOSURES under the jits: jax keys the pjit program
        # cache on the underlying callable, so wrapping the module-level
        # impls directly would make every engine in the process share one
        # cache and `_cache_size()` count other engines' programs. A fresh
        # function object per engine keeps the compile accounting local
        # (and the closed-over model off the static-argument hash path).
        def _prefill_bound(params: Any, cache: Any, *rest: Any, **named: Any) -> Any:
            return _prefill_impl(self.decode_model, params, cache, *rest, **named)

        def _decode_bound(params: Any, cache: Any, *rest: Any, **named: Any) -> Any:
            return _decode_impl(self.decode_model, params, cache, *rest, **named)

        def _verify_bound(params: Any, cache: Any, *rest: Any) -> Any:
            return _verify_impl(self.decode_model, params, cache, *rest)

        def _cow_bound(cache: Any, src: Any, dst: Any) -> Any:
            return _cow_impl(cache, src, dst)

        self._prefill_jit = jax.jit(_prefill_bound, donate_argnums=(1,))
        self._decode_jit = jax.jit(_decode_bound, donate_argnums=(1,))
        self._verify_jit = jax.jit(_verify_bound, donate_argnums=(1,))
        self._cow_jit = jax.jit(_cow_bound, donate_argnums=(0,))
        self._prefill_shapes: set[int] = set()
        self._decode_shapes: set[int] = set()
        self._verify_shapes: set[tuple[int, int]] = set()
        self._cow_used = False
        # Optional ``(name, **args) -> context manager`` (the scheduler's
        # timeline span): stage / dispatch / fetch of every call land as
        # children of the scheduler span that made the call.
        self.span_factory: Any = None

    def _span(self, phase: str, call: str, **counted: Any):
        if self.span_factory is None:
            return nullcontext()
        return self.span_factory(f"serve/engine.{phase}", call=call, **counted)

    def _first_call(self, shapes: set, key: Any, kind: str, bucket: int):
        """The call in which a shape is first seen runs under a
        ``startup/first_call`` span: JAX's own trace / lower / compile /
        cache-load events land inside it as spans (telemetry/timeline.py),
        and what they leave of it is the program's first execution."""
        if key in shapes:
            return nullcontext()
        shapes.add(key)
        return process_span("startup/first_call", kind=kind, bucket=bucket)

    # --------------------------------------------------------- validation

    def validate_request(self, prompt_len: int, max_new_tokens: int) -> str | None:
        """Why this engine can never serve the request, or None if it can.

        Checked at ADMISSION (scheduler) and at the HTTP boundary (400,
        not a late 500): the model's context bound, the largest prompt
        bucket (prefill cannot pad past it), and the pool's total
        capacity — a request whose worst-case block need exceeds the
        whole pool would otherwise sit at the FIFO head forever, starving
        everything behind it (try_reserve can only say "not yet").
        """
        prompt_len, total = int(prompt_len), int(prompt_len) + int(max_new_tokens)
        if total > self.block_size:
            return (
                f"prompt+max_new_tokens ({total}) exceeds the model "
                f"block_size ({self.block_size})"
            )
        if self.prefill_chunk == 0 and prompt_len > self.prompt_buckets[-1]:
            # Chunked prefill lifts this bound: chunks of <= prefill_chunk
            # tokens each pad into an existing bucket, so long prompts are
            # servable up to the block_size check above.
            return (
                f"prompt length ({prompt_len}) exceeds the largest "
                f"serving prompt bucket ({self.prompt_buckets[-1]})"
            )
        capacity = self.pool.num_blocks - 1
        need = self.pool.blocks_needed(total)
        if need > capacity:
            return (
                f"request needs {need} worst-case KV blocks but the pool "
                f"only holds {capacity} — raise serving.num_blocks or "
                f"lower max_new_tokens"
            )
        return None

    # ----------------------------------------------------------- stepping

    def prefill(
        self,
        prompt_ids: np.ndarray,  # (Tp,) int32 — the slab to run (suffix
        # of the prompt under prefix reuse / one chunk under chunking)
        table_padded: list[int],
        *,
        seed: int,
        temperature: float,
        top_k: int | None,
        top_p: float | None,
        offset: int = 0,  # absolute position of prompt_ids[0]
        params: Any | None = None,  # hot-swap: admitted-epoch params
        state_row: int = 0,  # the sequence's state row (models with state)
        window_table: list[int] | None = None,  # the sequence's ring (models with window layers)
    ) -> int:
        """Run one joining sequence's prompt slab; returns the token
        sampled at its last real position (the first output token when
        the slab ends the prompt; discarded by the caller for non-final
        chunks — one program either way, the bounded-compile contract)."""
        tp = int(prompt_ids.shape[0])
        tb = bucket_for(tp, self.prompt_buckets)
        with self._first_call(self._prefill_shapes, tb, "prefill", tb):
            counted = {"prompt_tokens": tp, "bucket": tb}
            if self.state_bytes_per_row:
                # The row is read (unless the slab starts the sequence) and written.
                counted["state_bytes"] = (2 if offset else 1) * self.state_bytes_per_row
                if self._scan_chunk:
                    counted["scan_chunks"] = -(-tb // self._scan_chunk)
            if self._selects:
                seen = np.arange(int(offset), int(offset) + tp, dtype=np.int64) + 1  # positions a query may see
                counted["index_pairs"] = int(seen.sum())
                counted["selected_pairs"] = int(np.minimum(seen, self._selects).sum())
            if self.window_tokens:
                if offset:
                    raise ValueError(
                        "a model with window layers prefills a prompt whole, from position 0 "
                        f"(got offset {offset}): a slab attends only its own keys"
                    )
                seen = np.arange(1, tp + 1, dtype=np.int64)  # keys a causal query at p sees: p + 1
                counted["causal_pairs"] = int(seen.sum())
                counted["window_pairs"] = int(np.minimum(seen, self.window_tokens).sum())
            named = {}
            with self._span("stage", "prefill", **counted):
                prompt = np.zeros((1, tb), np.int32)
                prompt[0, :tp] = prompt_ids
                staged = (
                    jnp.asarray(prompt),
                    jnp.asarray([tp], jnp.int32),
                    jnp.asarray([int(offset)], jnp.int32),
                    jnp.asarray([table_padded], jnp.int32),
                    jnp.asarray([seed & 0xFFFFFFFF], jnp.uint32),
                    jnp.asarray([temperature], jnp.float32),
                    jnp.asarray([0 if top_k is None else top_k], jnp.int32),
                    jnp.asarray([0.0 if top_p is None else top_p], jnp.float32),
                )
                if self.state_bytes_per_row:
                    staged += (jnp.asarray([int(state_row)], jnp.int32),)
                if self.window_tokens:
                    named["window_tables"] = jnp.asarray([window_table], jnp.int32)
            try:
                with self._span("dispatch", "prefill"):
                    cache, tok = self._prefill_jit(
                        self.params if params is None else params, self._cache, *staged, **named
                    )
            except Exception:
                self._recover_cache_after_error()
                raise
            self._cache = cache
            with self._span("fetch", "prefill"):
                return int(tok[0])

    def decode(
        self, rows: list[dict[str, Any]], *, params: Any | None = None
    ) -> list[int]:
        """Advance every row one token; returns next tokens, row-aligned.

        Each row dict: ``token`` (last emitted), ``position`` (its
        absolute position), ``table`` (padded physical ids), ``seed``,
        ``emit_idx``, ``temperature``, ``top_k``, ``top_p``, for a
        model with state leaves ``state_row`` and for one with window layers
        ``window_table`` (the padded ring). The batch is padded to a
        batch bucket with null-table, null-state-row greedy rows whose
        output is discarded.
        """
        n = len(rows)
        if n == 0:
            return []
        bb = bucket_for(n, self.batch_buckets)
        with self._first_call(self._decode_shapes, bb, "decode", bb):
            mb = self.max_blocks_per_seq

            def col(key: str, fill: Any, dtype: Any) -> np.ndarray:
                out = np.full((bb,), fill, dtype=dtype)
                for i, r in enumerate(rows):
                    out[i] = r[key]
                return out

            # Every padded row gathers its whole block table
            # (``_paged_decode_attention``), whatever the real rows attend.
            counted = {
                "kv_live_tokens": sum(int(r["position"]) + 1 for r in rows),
                "kv_gathered_tokens": bb * mb * self.block_tokens,
            }
            if self._kv_form is not None:
                counted["kv_form"] = self._kv_form
            if self.state_bytes_per_row:
                # What the call must move: each real row's state, read and written.
                counted["state_rows"] = n
                counted["state_bytes"] = 2 * n * self.state_bytes_per_row
            if self._selects:
                counted["kv_selected_tokens"] = sum(min(int(r["position"]) + 1, self._selects) for r in rows)
                counted["rows_past_topk"] = sum(int(r["position"]) + 1 > self._selects for r in rows)
            if self.window_tokens:
                counted["kv_window_tokens"] = sum(min(int(r["position"]) + 1, self.window_tokens) for r in rows)
                counted["kv_window_gathered_tokens"] = bb * self.window_ring * self.block_tokens
                counted["window_blocks_bound"] = self.pool.window_allocated_blocks
                counted["global_blocks_bound"] = self.pool.allocated_blocks
            named = {}
            with self._span("stage", "decode", **counted):
                tables = np.zeros((bb, mb), np.int32)
                for i, r in enumerate(rows):
                    tables[i] = r["table"]
                staged = (
                    jnp.asarray(col("token", 0, np.int32)),
                    jnp.asarray(col("position", 0, np.int32)),
                    jnp.asarray(tables),
                    jnp.asarray(
                        np.array(
                            [r["seed"] & 0xFFFFFFFF for r in rows] + [0] * (bb - n),
                            dtype=np.uint32,
                        )
                    ),
                    jnp.asarray(col("emit_idx", 0, np.int32)),
                    jnp.asarray(col("temperature", 0.0, np.float32)),
                    jnp.asarray(col("top_k", 0, np.int32)),
                    jnp.asarray(col("top_p", 0.0, np.float32)),
                )
                if self.state_bytes_per_row:
                    staged += (jnp.asarray(col("state_row", 0, np.int32)),)
                if self.window_tokens:
                    rings = np.zeros((bb, self.window_ring), np.int32)
                    for i, r in enumerate(rows):
                        rings[i] = r["window_table"]
                    named["window_tables"] = jnp.asarray(rings)
            try:
                with self._span("dispatch", "decode"):
                    cache, tok = self._decode_jit(
                        self.params if params is None else params, self._cache, *staged, **named
                    )
            except Exception:
                self._recover_cache_after_error()
                raise
            self._cache = cache
            with self._span("fetch", "decode") as counted:
                host = np.asarray(jax.device_get(tok))
                if self._counts_experts and counted is not None:
                    counted.update(zip(EXPERT_COUNTERS, map(int, host[bb:])))
                return [int(t) for t in host[:n]]

    def verify(
        self,
        rows: list[dict[str, Any]],
        *,
        width: int,
        params: Any | None = None,
    ) -> list[list[int]]:
        """Score a ``width``-token slab for every row in ONE bucketed call
        (batched speculative verify). Each row dict: ``tokens`` (width
        ints — last accepted token + the draft tokens), ``position``
        (tokens[0]'s absolute position), ``table``. Returns each row's
        per-position argmax — the target model's greedy continuation
        given every draft prefix. Writes the slab's K/V; rejected
        positions are simply overwritten when the corrected tokens are
        fed (position p maps to a fixed (block, slot), and queries never
        see past their own position — cursorless rollback)."""
        if self.state_bytes_per_row:
            raise ValueError(
                "verify (speculative decoding) cannot serve a model with "
                "recurrent state: rejected draft tokens would have moved the "
                "state and there is no rollback of a state row yet"
            )
        if self.window_tokens:
            raise ValueError(
                "verify (speculative decoding) cannot serve a model with window "
                "layers: a slab of draft tokens would read earlier keys through the "
                "window table, and a rejected draft may already have reused a ring entry"
            )
        n = len(rows)
        if n == 0:
            return []
        bb = bucket_for(n, self.batch_buckets)
        with self._first_call(self._verify_shapes, (bb, width), "verify", bb):
            mb = self.max_blocks_per_seq
            with self._span("stage", "verify"):
                tokens = np.zeros((bb, width), np.int32)
                positions = np.zeros((bb,), np.int32)
                tables = np.zeros((bb, mb), np.int32)
                for i, r in enumerate(rows):
                    if len(r["tokens"]) != width:
                        raise ValueError(
                            f"verify row {i} holds {len(r['tokens'])} tokens, "
                            f"expected width {width}"
                        )
                    tokens[i] = r["tokens"]
                    positions[i] = r["position"]
                    tables[i] = r["table"]
                staged = (jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(tables))
            try:
                with self._span("dispatch", "verify"):
                    cache, out = self._verify_jit(
                        self.params if params is None else params, self._cache, *staged
                    )
            except Exception:
                self._recover_cache_after_error()
                raise
            self._cache = cache
            with self._span("fetch", "verify"):
                host = np.asarray(jax.device_get(out))
                return [[int(t) for t in host[i]] for i in range(n)]

    def cow_copy(self, src: int, dst: int) -> None:
        """Device-side copy-on-write: pool block ``src`` → ``dst`` in every
        cache leaf. The pool's cow_last_shared() picks the pair; this is
        the write half of its contract (must run before the next pool
        mutation can recycle ``src``)."""
        if self.window_tokens:
            raise ValueError(
                "cow_copy cannot serve a model with window layers: a block id names "
                "a block of ONE of its two pools, and nothing shares a window block"
            )
        self._cow_used = True
        with self._span("stage", "cow_copy"):
            staged = (jnp.asarray([src], jnp.int32), jnp.asarray([dst], jnp.int32))
        try:
            # Nothing is read back, so there is no ``fetch``: the copy is
            # waited for by whichever later call reads the cache.
            with self._span("dispatch", "cow_copy"):
                self._cache = self._cow_jit(self._cache, *staged)
        except Exception:
            self._recover_cache_after_error()
            raise

    def _recover_cache_after_error(self) -> None:
        """Donation safety: a jitted call that fails at RUNTIME has already
        consumed (deleted) the donated cache buffers, so without recovery
        every later prefill/decode would die on "Array has been deleted" —
        one transient device error would wedge the server for good.
        Trace-time failures never donate: a still-live cache (and the
        in-flight KV it holds) is kept untouched; every deleted leaf, pool
        or state, is rebuilt zeroed (a live one is kept) and ``cache_epoch``
        bumped so the scheduler fails the in-flight sequences whose KV or
        recurrent state went with it.
        """

        def deleted(leaf: Any) -> bool:
            return isinstance(leaf, jax.Array) and leaf.is_deleted()

        if any(deleted(leaf) for leaf in jax.tree.leaves(self._cache)):
            self._cache = jax.tree.map(
                lambda leaf, s: jnp.zeros(s.shape, s.dtype) if deleted(leaf) else leaf,
                self._cache,
                self._cache_struct,
            )
            self.cache_epoch += 1

    # --------------------------------------------------------- accounting

    def cost_profile(
        self,
        *,
        peaks: dict[str, float] | None = None,
        full: bool = True,
        top_k: int = 10,
    ) -> list[dict[str, Any]]:
        """AOT cost profiles of the prefill/decode programs at their
        LARGEST buckets (worst-case per-step cost; smaller buckets are
        strictly cheaper). Nothing executes and nothing is donated —
        profiling works against abstract shapes, so the live cache and
        in-flight KV stay untouched. ``full=False`` skips the XLA compile
        (cost totals only). Failed profiles are dropped, not raised.
        """
        from ..telemetry import profiling

        if peaks is None:
            peaks = profiling.resolve_peaks()
        sds = jax.ShapeDtypeStruct
        param_structs = jax.tree.map(
            lambda x: sds(jnp.shape(x), x.dtype), self.params
        )
        cache_structs = jax.tree.map(
            lambda s: sds(s.shape, s.dtype), self._cache_struct
        )
        mb = self.max_blocks_per_seq
        tb = self.prompt_buckets[-1]
        bb = self.batch_buckets[-1]
        prefill_args = (
            param_structs,
            cache_structs,
            sds((1, tb), jnp.int32),   # prompt
            sds((1,), jnp.int32),      # true_len
            sds((1,), jnp.int32),      # offsets
            sds((1, mb), jnp.int32),   # block_tables
            sds((1,), jnp.uint32),     # seeds
            sds((1,), jnp.float32),    # temps
            sds((1,), jnp.int32),      # top_ks
            sds((1,), jnp.float32),    # top_ps
        ) + ((sds((1,), jnp.int32),) if self.state_bytes_per_row else ())
        ring = self.window_ring
        decode_args = (
            param_structs,
            cache_structs,
            sds((bb,), jnp.int32),     # tokens
            sds((bb,), jnp.int32),     # positions
            sds((bb, mb), jnp.int32),  # block_tables
            sds((bb,), jnp.uint32),    # seeds
            sds((bb,), jnp.int32),     # emit_idx
            sds((bb,), jnp.float32),   # temps
            sds((bb,), jnp.int32),     # top_ks
            sds((bb,), jnp.float32),   # top_ps
        ) + ((sds((bb,), jnp.int32),) if self.state_bytes_per_row else ())
        profiles: list[dict[str, Any]] = []
        for name, jitted, args, rows in (
            (f"prefill_T{tb}", self._prefill_jit, prefill_args, 1),
            (f"decode_B{bb}", self._decode_jit, decode_args, bb),
        ):
            if ring:  # the profilers take positional shapes: the ring tables go last
                jitted = jax.jit(lambda *a, _f=jitted: _f(*a[:-1], window_tables=a[-1]))
                args += (sds((rows, ring), jnp.int32),)
            if full:
                prof = profiling.aot_profile(
                    jitted, args, name=name, peaks=peaks, top_k=top_k
                )
            else:
                prof = profiling.lower_cost_profile(jitted, args, name=name)
            if prof is not None:
                profiles.append(prof)
        return profiles

    def compile_stats(self) -> dict[str, Any]:
        """Bucket usage + compiled-program counts (the bounded-compile
        contract: programs <= prompt_buckets + batch_buckets, asserted by
        tests and reported by the load harness). Optional programs widen
        the budget only when their feature is exercised: batched
        speculative verify adds at most one program per batch bucket per
        slab width used, and the COW copy is exactly one program — so
        chunked prefill adds NOTHING (chunks pad into existing prompt
        buckets) and the budget stays a static, assertable bound."""
        verify_widths = {w for _, w in self._verify_shapes}
        stats: dict[str, Any] = {
            "prompt_buckets": list(self.prompt_buckets),
            "batch_buckets": list(self.batch_buckets),
            "prefill_shapes_used": sorted(self._prefill_shapes),
            "decode_shapes_used": sorted(self._decode_shapes),
            "verify_shapes_used": sorted(self._verify_shapes),
            "budget": (
                len(self.prompt_buckets)
                + len(self.batch_buckets)
                + len(self.batch_buckets) * len(verify_widths)
                + (1 if self._cow_used else 0)
            ),
        }
        try:  # jax's own cache entry count, when the API exists (0.4.x)
            stats["prefill_programs"] = int(self._prefill_jit._cache_size())
            stats["decode_programs"] = int(self._decode_jit._cache_size())
            stats["verify_programs"] = int(self._verify_jit._cache_size())
            stats["cow_programs"] = int(self._cow_jit._cache_size())
        except Exception:  # noqa: BLE001 — accounting is best-effort
            stats["prefill_programs"] = len(self._prefill_shapes)
            stats["decode_programs"] = len(self._decode_shapes)
            stats["verify_programs"] = len(self._verify_shapes)
            stats["cow_programs"] = 1 if self._cow_used else 0
        if self.state_bytes_per_row:
            stats["state_leaves"] = self._state_leaves
            stats["state_rows"] = self.pool.state_rows
            stats["state_bytes_per_row"] = self.state_bytes_per_row
        if self.window_tokens:
            stats["window_tokens"] = self.window_tokens
            stats["window_ring_blocks"] = self.window_ring
            stats["window_num_blocks"] = self.pool.window_num_blocks
        stats["within_budget"] = (
            stats["prefill_programs"]
            + stats["decode_programs"]
            + stats["verify_programs"]
            + stats["cow_programs"]
            <= stats["budget"]
        )
        return stats

    # ---------------------------------------------------------- hot swap

    def set_params(self, params: Any) -> None:
        """Swap the default params between scheduler steps (checkpoint
        hot-swap). Callers that pin a request to its admitted params pass
        them explicitly to prefill/decode/verify instead — the jitted
        programs take params as a traced argument, so neither path
        recompiles. The prefix cache must be invalidated by the caller
        (scheduler) — cached K/V is a function of the OLD params. State
        rows need nothing: a row belongs to one request, which decodes on
        the params it was admitted under until it retires."""
        self.params = params


__all__ = [
    "PagedDecodeEngine",
    "bucket_for",
]
