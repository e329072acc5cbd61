"""BTF002 negative fixture: the blessed donation patterns — rebind in
the same statement, rebind before the next read, factory programs, and
the engine's self.cache = cache idiom. Expected findings: 0."""
import jax


def _step(params, toks, cache):
    return toks, toks, cache


class Engine:
    def __init__(self):
        self._decode = jax.jit(_step, donate_argnums=(2,))
        self._progs = {}

    def _prog(self, k):
        prog = self._progs.get(k)
        if prog is None:
            prog = jax.jit(_step, donate_argnums=(2,))
            self._progs[k] = prog
        return prog

    def same_statement_rebind(self, params, toks, cache):
        nxt, logits, cache = self._decode(params, toks, cache)
        return nxt, cache.lengths       # rebound: reads the NEW buffer

    def attr_rebind(self, params, toks):
        nxt, logits, cache = self._decode(params, toks, self.cache)
        self.cache = cache              # store clears the poison
        return nxt, self.cache.lengths

    def factory_inline(self, params, toks, k):
        nxt, logits, cache = self._prog(k)(params, toks, self.cache)
        self.cache = cache
        return nxt

    def chained_loop(self, params, toks, cache):
        out = []
        for _ in range(4):
            nxt, logits, cache = self._decode(params, toks, cache)
            out.append(nxt)
        return out, cache


def _step_win(params, toks, cache, window, wlen):
    return toks, toks, cache, window, wlen


class WindowEngine:
    """Blessed window-carry pattern (ISSUE 12): every donated carry —
    cache, staged-window buffer, staged count — is rebound from the
    result before any later read (serving.py mixed_block_async)."""

    def __init__(self):
        self._win_progs = {}
        self._flush = jax.jit(_step_win, donate_argnums=(2, 4))

    def _win_prog(self, k):
        prog = self._win_progs.get(k)
        if prog is None:
            prog = jax.jit(_step_win, donate_argnums=(2, 3, 4))
            self._win_progs[k] = prog
        return prog

    def windowed_dispatch(self, params, toks, k):
        blk, fin, cache, window, wlen = self._win_prog(k)(
            params, toks, self.cache, self._window, self._wlen)
        self.cache, self._window, self._wlen = cache, window, wlen
        return blk, self._window.width

    def flush(self, params, toks):
        blk, fin, cache, window, wlen = self._flush(
            params, toks, self.cache, self._window, self._wlen)
        self.cache, self._wlen = cache, wlen
        return self._window         # NOT donated by the flush: clean read


def _step_spec(params, hist, cache, dstate):
    return hist, hist, cache, dstate


class DraftEngine:
    """Blessed draft-carry pattern (ISSUE 14): the spec program donates
    the history AND the draft-model KV cache; both rebind from the
    result before any later read (as serving.py
    mixed_spec_block_async's history and cursor do)."""

    def __init__(self):
        self._spec_progs = {}

    def _spec_prog(self, r):
        prog = self._spec_progs.get(r)
        if prog is None:
            prog = jax.jit(_step_spec, donate_argnums=(1, 3))
            self._spec_progs[r] = prog
        return prog

    def spec_dispatch(self, params, r):
        toks, hist, cache, dstate = self._spec_prog(r)(
            params, self._hist, self.cache, self._draft_state)
        self._hist, self.cache, self._draft_state = hist, cache, dstate
        return toks, self._draft_state.length


def _step_mixed(params, toks, cursor, cache, pbuf):
    return toks, toks, cursor, cache


class MixedEngine:
    """Blessed mixed-dispatch pattern (ISSUE 18): every donated carry —
    the prefill chunk-offset cursor AND the cache — rebinds from the
    result before any later read; the prompt buffer is NOT donated, so
    reading (or host-editing) it after the dispatch is clean
    (serving.py mixed_block_async)."""

    def __init__(self):
        self._mixed_progs = {}

    def _mixed_prog(self, k):
        prog = self._mixed_progs.get(k)
        if prog is None:
            prog = jax.jit(_step_mixed, donate_argnums=(2, 3))
            self._mixed_progs[k] = prog
        return prog

    def mixed_dispatch(self, params, toks, k):
        blk, fin, cursor, cache = self._mixed_prog(k)(
            params, toks, self._cursor, self.cache, self._pbuf)
        self._cursor, self.cache = cursor, cache
        return blk, self._cursor, self._pbuf  # all rebound / non-donated


def _step_tree(params, hist, cache, dstate, window, wlen):
    return hist, hist, cache, dstate, window, wlen


class TreeEngine:
    """Blessed tree-carry pattern (ISSUE 19): history + cache + draft
    KV state + staged tree-KV window + count ALL rebind from the
    result before any later read (as serving.py
    mixed_spec_block_async's windowed path rebinds its five)."""

    def __init__(self):
        self._tree_progs = {}

    def _tree_prog(self, r):
        prog = self._tree_progs.get(r)
        if prog is None:
            prog = jax.jit(_step_tree, donate_argnums=(1, 3, 4, 5))
            self._tree_progs[r] = prog
        return prog

    def tree_dispatch(self, params, r):
        toks, hist, cache, dstate, window, wlen = self._tree_prog(r)(
            params, self._hist, self.cache, self._draft_state,
            self._window, self._wlen)
        self._hist, self.cache = hist, cache
        self._draft_state, self._window, self._wlen = \
            dstate, window, wlen
        return toks, self._window.width  # all rebound: clean reads
