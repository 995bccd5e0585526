"""The two forms of the paged cache's read (models/gpt.py ``paged_kv_form``).

A decode call contracts q against the gathered blocks as the pool's own
rows (``"rows"``); a prefill or chunk call keeps the per-head form
(``"heads"``). Both must give what single-sequence linear decode gives,
for every pool layout the rule branches on, and the rule itself is a
function of static shapes alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmtrain_tpu.models import gpt
from llmtrain_tpu.models.gpt import (
    PAGED_ROWS_QUERY_LIMIT,
    CausalSelfAttention,
    paged_block_fold,
    paged_kv_form,
)

BLOCK_TOKENS, MAX_BLOCKS = 8, 12  # a row's table covers 96 positions
DEPTHS = (0, 5, 17)  # rows of one call start at different depths

# (query heads, K/V heads, head width, rotary): the row of a pool leaf is
# ``K/V heads x head width`` lanes.
LAYOUTS = {
    # MHA, 64-wide heads, an even count: a row of 256, two lane tiles.
    "mha64-even": (4, 4, 64, False),
    # An odd count: a row of 320, two and a half lane tiles, as gpt2-xl's
    # 1,600 is twelve and a half.
    "mha64-odd": (5, 5, 64, False),
    "mha64-odd-rotary": (5, 5, 64, True),
    # GQA with 128-wide heads (Falcon-H1's attention): a row of 256.
    "gqa128-rotary": (4, 2, 128, True),
    "gqa128": (4, 2, 128, False),
    # MQA at 64: half a lane tile, two positions fold into a pool row.
    "mqa64-fold2-rotary": (4, 1, 64, True),
}


def _switch_t(n_heads: int) -> int:
    """The largest t the rule still sends the new way at these heads."""
    return PAGED_ROWS_QUERY_LIMIT // n_heads


def _modules(layout: str):
    n_heads, kv_heads, head_dim, rope = LAYOUTS[layout]
    common = dict(
        d_model=48, n_heads=n_heads, n_layers=1, dropout=0.0, dtype=jnp.float32, param_dtype=jnp.float32,
        n_kv_heads=0 if kv_heads == n_heads else kv_heads, head_dim=head_dim, rope=rope, decode=True,
    )
    paged = CausalSelfAttention(
        paged=True, paged_num_blocks=1 + len(DEPTHS) * MAX_BLOCKS, paged_block_tokens=BLOCK_TOKENS, **common
    )
    linear = CausalSelfAttention(cache_len=BLOCK_TOKENS * MAX_BLOCKS, **common)
    return paged, linear


def _force(monkeypatch, form: str):
    monkeypatch.setattr(gpt, "paged_kv_form", lambda **_: form)


@pytest.mark.parametrize("at", ["t1", "switch", "past-switch"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_rows_form_agrees_with_the_per_head_form_and_with_linear_decode(monkeypatch, layout, at):
    n_heads, kv_heads, head_dim, _ = LAYOUTS[layout]
    t = {"t1": 1, "switch": _switch_t(n_heads), "past-switch": _switch_t(n_heads) + 1}[at]
    fold = paged_block_fold(BLOCK_TOKENS, kv_heads * head_dim)
    chosen = paged_kv_form(
        t=t, n_heads=n_heads, kv_heads=kv_heads, head_dim=head_dim, block_tokens=BLOCK_TOKENS
    )
    # The rule: the new form up to the switch where a position is a whole
    # pool row, the per-head form past it and wherever positions fold.
    assert chosen == ("rows" if fold == 1 and at != "past-switch" else "heads")

    paged, linear = _modules(layout)
    rng = np.random.default_rng(7)
    history = [jnp.asarray(rng.standard_normal((1, d, 48)), jnp.float32) for d in DEPTHS]
    slab = jnp.asarray(rng.standard_normal((len(DEPTHS), t, 48)), jnp.float32)
    tables = jnp.asarray(
        [[1 + i * MAX_BLOCKS + j for j in range(MAX_BLOCKS)] for i in range(len(DEPTHS))], jnp.int32
    )
    variables = linear.init(jax.random.key(0), slab[:1, :1])
    params = variables["params"]

    # Single-sequence linear decode: a row's history, then its slab.
    expected = []
    for i, past in enumerate(history):
        cache = jax.tree.map(jnp.zeros_like, variables["cache"])
        if past.shape[1]:
            _, mutated = linear.apply({"params": params, "cache": cache}, past, mutable=["cache"])
            cache = mutated["cache"]
        out, _ = linear.apply({"params": params, "cache": cache}, slab[i : i + 1], mutable=["cache"])
        expected.append(out)
    expected = jnp.concatenate(expected)

    # The pool: every row's history written through the per-head form, one
    # row a call (what a prefill call is).
    _force(monkeypatch, "heads")
    pool = jax.tree.map(
        jnp.zeros_like,
        paged.init(
            jax.random.key(0), slab[:1, :1], positions=jnp.zeros((1,), jnp.int32), block_tables=tables[:1]
        )["cache"],
    )
    for i, past in enumerate(history):
        if past.shape[1]:
            _, mutated = paged.apply(
                {"params": params, "cache": pool}, past, positions=jnp.zeros((1,), jnp.int32),
                block_tables=tables[i : i + 1], mutable=["cache"],
            )
            pool = mutated["cache"]

    def call(form):
        _force(monkeypatch, form)
        out, mutated = paged.apply(
            {"params": params, "cache": pool}, slab, positions=jnp.asarray(DEPTHS, jnp.int32),
            block_tables=tables, mutable=["cache"],
        )
        return out, mutated["cache"]

    per_head, written = call("heads")
    np.testing.assert_allclose(per_head, expected, rtol=2e-5, atol=2e-5)
    if fold == 1:
        rows, written_rows = call("rows")
        np.testing.assert_allclose(rows, per_head, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(rows, expected, rtol=2e-5, atol=2e-5)
        # The write is the same whatever reads it.
        jax.tree.map(np.testing.assert_array_equal, written_rows, written)
    # What the rule picks on its own is one of the two.
    monkeypatch.undo()
    ruled, _ = paged.apply(
        {"params": params, "cache": pool}, slab, positions=jnp.asarray(DEPTHS, jnp.int32),
        block_tables=tables, mutable=["cache"],
    )
    np.testing.assert_allclose(ruled, expected, rtol=2e-5, atol=2e-5)


def test_rows_form_is_bitwise_the_per_head_form_in_bfloat16(monkeypatch):
    """Zeros add nothing to a float32 accumulation: with bf16 operands the
    two forms round the same sums at the same places."""
    n_heads, kv_heads, head_dim = 5, 5, 64
    rng = np.random.default_rng(3)
    module = CausalSelfAttention(
        d_model=64, n_heads=n_heads, n_layers=1, dropout=0.0, dtype=jnp.bfloat16, param_dtype=jnp.float32,
        head_dim=head_dim, decode=True, paged=True, paged_num_blocks=1 + 3 * MAX_BLOCKS,
        paged_block_tokens=BLOCK_TOKENS,
    )
    x = jnp.asarray(rng.standard_normal((3, 1, 64)), jnp.float32)
    tables = jnp.asarray([[1 + i * MAX_BLOCKS + j for j in range(MAX_BLOCKS)] for i in range(3)], jnp.int32)
    positions = jnp.asarray([3, 40, 95], jnp.int32)
    variables = module.init(jax.random.key(1), x, positions=positions, block_tables=tables)
    pool = jax.tree.map(
        lambda leaf: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype), variables["cache"]
    )
    outs = {}
    for form in ("heads", "rows"):
        monkeypatch.setattr(gpt, "paged_kv_form", lambda form=form, **_: form)
        outs[form], _ = module.apply(
            {"params": variables["params"], "cache": pool}, x, positions=positions, block_tables=tables,
            mutable=["cache"],
        )
    np.testing.assert_array_equal(np.asarray(outs["rows"], np.float32), np.asarray(outs["heads"], np.float32))


# The calls the benchmark's three cells that run this function make
# (BENCHMARK.json: decode, the prompt buckets), and a speculative verify of
# k = 4 drafts on each.
CELL_CALLS = {
    # (n_heads, kv_heads, head_dim)
    "gpt2-small.serve-batch": (12, 12, 64),
    "gpt2-xl.serve-chat": (25, 25, 64),
    "falcon-h1-34b.serve-batch": (20, 4, 128),
}


@pytest.mark.parametrize("cell", list(CELL_CALLS))
def test_the_rule_on_the_cells_decode_prefill_and_verify_calls(cell):
    n_heads, kv_heads, head_dim = CELL_CALLS[cell]

    def form(t):
        return paged_kv_form(t=t, n_heads=n_heads, kv_heads=kv_heads, head_dim=head_dim, block_tokens=16)

    assert form(1) == "rows"  # a decode call: every slot's whole table
    for bucket in (128, 256, 640):  # a prefill or chunk call: one prompt
        assert form(bucket) == "heads"
    assert form(5) == "rows"  # verify, k + 1 = 5


def test_the_rule_reads_nothing_but_its_arguments():
    """A row under one lane tile keeps the per-head form whatever t is; the
    switch is in query rows a gathered position, t x heads."""
    mqa = dict(n_heads=12, kv_heads=1, head_dim=64, block_tokens=16)
    assert paged_block_fold(16, 64) == 2
    assert paged_kv_form(t=1, **mqa) == "heads"
    gqa = dict(n_heads=12, kv_heads=4, head_dim=64, block_tokens=16)
    assert paged_kv_form(t=1, **gqa) == "rows"
    assert paged_kv_form(t=PAGED_ROWS_QUERY_LIMIT // 12, **gqa) == "rows"
    assert paged_kv_form(t=PAGED_ROWS_QUERY_LIMIT // 12 + 1, **gqa) == "heads"
