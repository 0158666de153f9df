"""Model scopes (``repro.models.scopes``) are op metadata only.

The serving programs name their layers with ``jax.named_scope`` so that a
profiler trace can put device time down to layers.  A scope may change
nothing but each instruction's metadata: the compiled decode window and
prefill must be the same op for op with the scope function replaced by
``contextlib.nullcontext``.  And the work that matters must carry a
scope: every dot and gather of the decode window names one.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.models import Model, load_reduced, scopes
from repro.models.config import QuantPolicy
from repro.serve import ContinuousBatchingEngine

SLOTS, PAGE, MAX_LEN, SYNC = 2, 8, 32, 4


def _engine():
    cfg = load_reduced("chatglm3_6b", mx=QuantPolicy.parse("kv=int8@32:ocp"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ContinuousBatchingEngine(model, params, max_slots=SLOTS,
                                    page_size=PAGE, max_len=MAX_LEN,
                                    sync_every=SYNC)


def _compiled(eng):
    """(decode window, prefill) optimized HLO text of a fresh engine."""
    vec = jnp.zeros(SLOTS, jnp.int32)
    bt = jnp.asarray(eng.blocks.tables)
    multi = eng._multi.lower(eng.params, vec, eng.pool, bt, vec, vec,
                             eng._slot_keys, SYNC).compile().as_text()
    lp = 2 * PAGE
    prefill = eng._prefill_scatter.lower(
        eng.params, jnp.zeros((1, lp), jnp.int32),
        jnp.full((1,), lp - 3, jnp.int32), jnp.zeros((1, 2), jnp.uint32),
        eng.pool, jnp.zeros((1, lp // PAGE), jnp.int32)).compile().as_text()
    return multi, prefill


def _strip(text):
    """Drop what is metadata: each instruction's ``metadata={...}`` and
    the module's stack-frame table (FileNames ... StackFrames)."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return re.sub(r"\nFileNames\n.*?\n(?=%|ENTRY)", "\n", text, flags=re.S)


@pytest.fixture(scope="module")
def programs():
    scoped = _compiled(_engine())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scopes, "scope", contextlib.nullcontext)
        bare = _compiled(_engine())
    return scoped, bare


@pytest.mark.parametrize("i,program", [(0, "_multi"),
                                       (1, "_prefill_scatter")])
def test_scopes_change_only_metadata(programs, i, program):
    scoped, bare = programs[0][i], programs[1][i]
    assert "/kv_write/" in scoped and "/kv_write/" not in bare, program
    a, b = _strip(scoped), _strip(bare)
    assert "metadata=" not in a
    assert a.splitlines() == b.splitlines(), program


def _op_names(text, ops):
    pat = re.compile(r"= [^=]* (%s)\(" % "|".join(ops))
    for line in text.splitlines():
        if pat.search(line):
            m = re.search(r'op_name="([^"]*)"', line)
            yield line.strip(), m.group(1) if m else ""


def test_every_dot_and_gather_of_the_decode_window_has_a_scope(programs):
    found = list(_op_names(programs[0][0], ("dot", "gather")))
    assert len(found) >= 10
    for line, path in found:
        parts = {p.split(":", 1)[0] for p in path.split("/")}
        assert parts & set(scopes.SCOPES), line
    named = {next(p for p in reversed(path.split("/"))
                  if p in scopes.SCOPES) for _, path in found}
    assert {"attn_qkv", "kv_read", "attn_core", "attn_out", "mlp", "head",
            "embed"} <= named


def test_scope_names_are_checked():
    with pytest.raises(ValueError):
        scopes.scope("kv-read")
    with pytest.raises(ValueError):
        scopes.scoped("decode")
