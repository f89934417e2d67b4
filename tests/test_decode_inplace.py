"""The decode step writes each row's new key and value, or MLA latent and
rope key, into the carried, donated cache in place, and computes exactly
what writing into a per-layer copy and attending to it computes.

The oracle is the decode step as the layer loop ran it before: every
period layer's cache enters the loop as its per-layer input and leaves as
a whole new layer, and attention is ``write_kv_cache`` then
``decode_attention_ref`` (MLA: ``write_latent_token`` then
``latent_attention``) on that layer alone. Logits and caches must agree
bit for bit, on the scanned and the unrolled layer loop, for attention and
MLA layers (carried) and for recurrent layers (still per-layer)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, model_defs
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import ServeEngine

B, S, STEPS = 4, 16, 8
# a row at 0, two in the middle, and one whose write reaches S-1 at the end
LENGTHS = (0, 5, 9, S - STEPS)


def oracle_decode_step(cfg, params, cache, tokens, unroll=False,
                       with_counts=False):
    """Write-then-attend decode, each layer on its own cache slice; with
    ``with_counts``, also the MoE layers' counts, as ``decode_step``."""
    flags = T.RunFlags()
    lengths = cache["lengths"]
    dt = jnp.dtype(cfg.dtype)
    x = params["embed"]["tok"].astype(dt)[tokens[:, None]]
    x = x * jnp.asarray(cfg.embedding_multiplier, dt)
    if cfg.pos_emb == "sincos":
        x = x + L.sincos_pos_emb(lengths[:, None], cfg.d_model).astype(dt)
    pre, pre_counts = [], []
    for spec, p, c in zip(cfg.prelayers, params["prelayers"],
                          cache["prelayers"]):
        x, c, n = T.apply_layer_decode(cfg, spec, p, x, c, lengths, flags)
        pre.append(c)
        pre_counts.append(n)

    def body(x, pc):
        new, counts = [], []
        for spec, p, c in zip(cfg.period, *pc):
            x, c, n = T.apply_layer_decode(cfg, spec, p, x, c, lengths,
                                           flags)
            new.append(c)
            counts.append(n)
        return x, (tuple(new), tuple(counts))

    x, (period, counts) = jax.lax.scan(
        body, x, (params["period"], cache["period"]), unroll=unroll)
    x = L.apply_norm(cfg, params["out_norm"], x)
    logits = L.unembed(cfg, params["embed"], x[:, 0])
    new_cache = {"prelayers": tuple(pre), "period": period,
                 "lengths": lengths + 1}
    if with_counts:
        return logits, new_cache, T.moe_counts(pre_counts + list(counts))
    return logits, new_cache


def _filled_cache(cfg):
    """A cache with random contents everywhere, lengths ragged."""
    cache = T.init_cache(cfg, B, S)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [(0.5 * jax.random.normal(k, a.shape)).astype(a.dtype)
              for a, k in zip(leaves, keys)]
    cache = jax.tree.unflatten(tree, leaves)
    cache["lengths"] = jnp.asarray(LENGTHS, jnp.int32)
    return cache


def _assert_same(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b), what
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), what


@pytest.mark.parametrize("unroll", [False, True], ids=["scan", "unroll"])
@pytest.mark.parametrize("arch", [
    "internlm2-1.8b",           # attention: carried, written in place
    "jamba-1.5-large-398b",     # mamba layers around one attention layer
    "xlstm-125m",               # mlstm and slstm state, replaced whole
    "deepseek-v2-236b",         # MLA latents, and an unscanned prelayer
    "deepseek-v2-lite-ep8",     # the same with YaRN and held experts
])
def test_decode_matches_write_then_attend(arch, unroll):
    cfg = get_config(arch, smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    flags = T.RunFlags(unroll_layers=unroll)
    new = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t, flags=flags))
    old = jax.jit(lambda p, c, t: oracle_decode_step(cfg, p, c, t, unroll))
    c_new = c_old = _filled_cache(cfg)
    for step in range(STEPS):
        tok = jax.random.randint(jax.random.PRNGKey(10 + step), (B,), 0,
                                 cfg.vocab_size)
        lg_new, c_new = new(params, c_new, tok)
        lg_old, c_old = old(params, c_old, tok)
        _assert_same(lg_new, lg_old, f"logits, step {step}")
        _assert_same(c_new, c_old, f"cache, step {step}")
    assert int(c_new["lengths"][-1]) == S     # the last write was at S-1


def _oracle_engine(cfg, params, **kw):
    """An engine that drives the oracle step, donating nothing (and
    returning the MoE counts, as the engine's own step does)."""
    engine = ServeEngine(cfg, params, **kw)

    def step(p, owned, kept, t):
        period = tuple(k if o is None else o
                       for o, k in zip(owned, kept["period"]))
        return oracle_decode_step(cfg, p, dict(kept, period=period), t,
                                  with_counts=cfg.moe is not None)

    engine._decode = jax.jit(step)
    return engine


def _serve_both(cfg, donating, oracle):
    rng = np.random.RandomState(0)
    batches = [[list(rng.randint(1, cfg.vocab_size, size=n))
                for n in (3, 9, 5, 12, 1)],
               [list(rng.randint(1, cfg.vocab_size, size=n))
                for n in (7, 2, 4)]]
    for prompts in batches:
        got = donating.run(prompts, max_new=6)
        want = oracle.run(prompts, max_new=6)
        assert [r.tokens for r in got] == [r.tokens for r in want]
    assert donating.counters == oracle.counters


def test_engine_greedy_tokens_match_oracle_loop():
    """The donating engine serves the same greedy tokens as the same engine
    driving the oracle step without donation, across admissions spliced
    between steps and across two ``run`` calls on one engine; and a cache
    array taken before a ``step`` is gone after it (the program owns it)."""
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    donating = ServeEngine(cfg, params, max_batch=2, max_seq=32)
    oracle = _oracle_engine(cfg, params, max_batch=2, max_seq=32)
    _serve_both(cfg, donating, oracle)

    assert donating.add_request([5, 6, 7], max_new=3) is not None
    kept = donating.cache["period"][0]["k"]
    lengths = donating.cache["lengths"]
    donating.step()
    assert kept.is_deleted()                 # donated to the decode program
    assert not lengths.is_deleted()          # kept for the freed-slot pin


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b",
                                  "deepseek-v2-236b"])
def test_engine_donates_only_carried_caches(arch):
    """Where the program reads and replaces a cache whole (mamba state, an
    unscanned prelayer's latents), the engine keeps it out of the
    donation, and donates the attention caches and period MLA latents it
    carries: those arrays are gone after a step, the rest are kept. It
    serves the oracle's tokens either way."""
    cfg = get_config(arch, smoke=True)
    params = init_params(model_defs(cfg), jax.random.PRNGKey(0))
    donating = ServeEngine(cfg, params, max_batch=2, max_seq=32)
    oracle = _oracle_engine(cfg, params, max_batch=2, max_seq=32)
    _serve_both(cfg, donating, oracle)

    assert donating.add_request([5, 6, 7], max_new=3) is not None
    before = donating.cache
    carried = T.carried_layers(cfg, T.RunFlags())
    assert any(carried)     # Jamba's attention layer, DeepSeek's latents
    donating.step()
    for c, k in zip(before["period"], carried):
        assert all(a.is_deleted() == k for a in jax.tree.leaves(c))
    assert not any(a.is_deleted() for a in jax.tree.leaves(
        before["prelayers"]))
