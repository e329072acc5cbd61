"""BTF002 positive fixture: reads of donated references after dispatch.

Expected findings: 8 —
* a read of the donated cache in the statement after the dispatch,
* the same handle re-passed on the next loop iteration without rebind,
* a read of a tree donated to a locally-built donating jit,
* a window-carry dispatch (ISSUE 12: factory program donating the
  cache AND the staged-window buffers) that rebinds the cache but
  reads the donated window attribute afterwards,
* a spec-block dispatch (ISSUE 14: factory program donating the
  history carry AND the draft-model KV cache) that rebinds the
  history but reads the donated draft cache afterwards,
* a mixed-dispatch block (ISSUE 18: factory program donating the
  per-slot prefill chunk-offset cursor alongside the cache) that
  rebinds the cache but reads the stale cursor afterwards,
* a tree-speculation dispatch (ISSUE 19: factory program donating the
  history carry, the draft KV state, AND the staged tree-KV window +
  count) that rebinds everything except the window and then reads the
  stale tree K/V,
* a seq-parallel chunk-prefill dispatch (ISSUE 20: factory program
  donating the paged KV pool AND the per-slot length vector) that
  rebinds the pool but reads the donated lengths afterwards.
"""
import jax


def _step(params, toks, cache):
    return toks, toks, cache


class Engine:
    def __init__(self):
        self._decode = jax.jit(_step, donate_argnums=(2,))

    def read_after_dispatch(self, params, toks, cache):
        nxt, logits, new_cache = self._decode(params, toks, cache)
        return nxt, cache.lengths                     # finding 1

    def stale_loop_operand(self, params, toks, cache):
        out = []
        for _ in range(4):
            # donates `cache` but rebinds `cache2`: iteration t+1
            # passes the freed buffer again
            nxt, logits, cache2 = self._decode(params, toks, cache)
            out.append(nxt)                           # finding 2 (cache)
        return out


def local_jit(tree):
    cast = jax.jit(lambda p: p, donate_argnums=(0,))
    out = cast(tree)
    return out, tree                                  # finding 3


def _step_win(params, toks, cache, window, wlen):
    return toks, toks, cache, window, wlen


class WindowEngine:
    """The write-combined-window carry: one program donates the cache
    AND the staged-window buffer + count (serving.py's
    _mixed_block_prog shape)."""

    def __init__(self):
        self._win_progs = {}

    def _win_prog(self, k):
        prog = self._win_progs.get(k)
        if prog is None:
            prog = jax.jit(_step_win, donate_argnums=(2, 3, 4))
            self._win_progs[k] = prog
        return prog

    def stale_window_read(self, params, toks, k):
        blk, fin, cache, window, wlen = self._win_prog(k)(
            params, toks, self.cache, self._window, self._wlen)
        self.cache = cache          # cache rebound...
        return blk, self._window    # finding 4: window NOT rebound


def _step_spec(params, hist, cache, dstate):
    return hist, hist, cache, dstate


class DraftEngine:
    """A spec block's two carries: one program donates the
    token-history carry AND a second device state (serving.py's
    _mixed_spec_prog shape: history and cursor)."""

    def __init__(self):
        self._spec_progs = {}

    def _spec_prog(self, r):
        prog = self._spec_progs.get(r)
        if prog is None:
            prog = jax.jit(_step_spec, donate_argnums=(1, 3))
            self._spec_progs[r] = prog
        return prog

    def stale_draft_cache_read(self, params, r):
        toks, hist, cache, dstate = self._spec_prog(r)(
            params, self._hist, self.cache, self._draft_state)
        self._hist = hist               # history rebound...
        self.cache = cache
        return toks, self._draft_state  # finding 5: draft NOT rebound


def _step_mixed(params, toks, cursor, cache, pbuf):
    return toks, toks, cursor, cache


class MixedEngine:
    """The mixed-dispatch carry (ISSUE 18): one program donates the
    per-slot prefill chunk-offset cursor AND the cache (serving.py's
    _mixed_block_prog shape); the prompt buffer is not donated."""

    def __init__(self):
        self._mixed_progs = {}

    def _mixed_prog(self, k):
        prog = self._mixed_progs.get(k)
        if prog is None:
            prog = jax.jit(_step_mixed, donate_argnums=(2, 3))
            self._mixed_progs[k] = prog
        return prog

    def stale_cursor_read(self, params, toks, k):
        blk, fin, cursor, cache = self._mixed_prog(k)(
            params, toks, self._cursor, self.cache, self._pbuf)
        self.cache = cache          # cache rebound...
        return blk, self._cursor    # finding 6: cursor NOT rebound


def _step_tree(params, hist, cache, dstate, window, wlen):
    return hist, hist, cache, dstate, window, wlen


class TreeEngine:
    """The tree-speculation window carry (ISSUE 19): one program
    donates the history carry, the draft KV state, AND the staged
    tree-KV window + count (serving.py's _mixed_spec_win_prog shape —
    rejected drafts live only in the window, so a stale window read
    is a read of freed K/V)."""

    def __init__(self):
        self._tree_progs = {}

    def _tree_prog(self, r):
        prog = self._tree_progs.get(r)
        if prog is None:
            prog = jax.jit(_step_tree, donate_argnums=(1, 3, 4, 5))
            self._tree_progs[r] = prog
        return prog

    def stale_tree_window_read(self, params, r):
        toks, hist, cache, dstate, window, wlen = self._tree_prog(r)(
            params, self._hist, self.cache, self._draft_state,
            self._window, self._wlen)
        self._hist, self.cache = hist, cache
        self._draft_state, self._wlen = dstate, wlen
        return toks, self._window   # finding 7: tree window NOT rebound


def _step_sp(params, chunk, cache, lengths, table):
    return chunk, cache, lengths


class SeqParallelEngine:
    """The seq-parallel chunk-prefill carry (ISSUE 20): one program
    donates the paged KV pool AND the per-slot length vector
    (serving.py's _sp_chunk_prog shape); the chunk operand and the
    page table are not donated."""

    def __init__(self):
        self._sp_progs = {}

    def _sp_prog(self, c):
        prog = self._sp_progs.get(c)
        if prog is None:
            prog = jax.jit(_step_sp, donate_argnums=(2, 3))
            self._sp_progs[c] = prog
        return prog

    def stale_length_read(self, params, chunk, c):
        logits, cache, lengths = self._sp_prog(c)(
            params, chunk, self.cache, self._lengths, self._table)
        self.cache = cache            # pool rebound...
        return logits, self._lengths  # finding 8: lengths NOT rebound
