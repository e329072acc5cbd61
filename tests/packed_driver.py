"""What engine._packed_scan does around one packed step, by hand, for
the tests of a model with recurrent layers (tests/test_granite_hybrid.py:
Mamba-2; tests/test_olmo_hybrid.py: Gated DeltaNet; tests/test_jamba.py:
Mamba-1) and, for the run with an idle chunk, of one with attention
alone (tests/test_mixed_dispatch.py): the driver, the
scripted run both families go through, and the readers of a parameter
tree and of an error that both compare with."""
import jax
import jax.numpy as jnp
import numpy as np

from butterfly_tpu.cache.paged import (
    flush_paged_window, init_kv_window, init_paged_cache, paged_forward,
    paged_forward_packed)
from butterfly_tpu.cache.ssm_state import init_ssm_state
from butterfly_tpu.core.config import RuntimeConfig
from butterfly_tpu.models.common import forward
from butterfly_tpu.quant.int8 import is_quantized_leaf

#: an eager lax.scan compiles its body at every call (its closures are
#: new functions each time): the tests drive jitted programs, one compile
#: a shape, as the engines do
forward = jax.jit(forward, static_argnums=(1,))
_packed_step = jax.jit(
    lambda params, cfg, *a, state, use_kernel=False: paged_forward_packed(
        params, cfg, *a, state=state, use_kernel=use_kernel),
    static_argnums=(1,), static_argnames=("use_kernel",))

#: a chunk's width in the scripted run
C = 6


def leaf_of(params):
    def leaf(path, layer=None):
        node = params
        for key in path.split("/"):
            node = node[key]
        if is_quantized_leaf(node):
            q8, s = node["q8"], node["s"]
            if layer is not None:
                q8, s = q8[layer], s[layer]
            return q8.astype(jnp.float32) * s.astype(jnp.float32)
        return (node if layer is None else node[layer]).astype(jnp.float32)
    return leaf


def err(got, want):
    """rms difference over the std of the reference's row."""
    d = np.asarray(got, np.float64) - want
    return float(np.sqrt(np.mean(d * d)) / np.std(want))


RT = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=4)


class Packed:
    """What engine._packed_scan does around one packed step, by hand:
    three slots with a page-table row each, the KV window and its flush
    every third step, the recurrent state through every step."""

    def __init__(self, params, cfg, windowed=True, width=C,
                 use_kernel=False, rt=RT, idle=0, ring=0):
        self.params, self.cfg, self.C = params, cfg, width
        # chunks a step carries BESIDE the one `step` fills, all idle
        # (count 0, slot 0: the scheduler's argmax of nothing), as a
        # step has them when prefill_inline_budget holds several chunks
        self.idle = idle
        self.use_kernel = use_kernel
        # ring: the pages of a sliding layer's ring a slot, where the
        # cache keeps those layers' rows apart (cache/paged.py ring_pages)
        cache = init_paged_cache(cfg, rt, ring=ring)
        S, mp = cache.page_table.shape
        self.cache = cache._replace(page_table=jnp.arange(
            S * mp, dtype=jnp.int32).reshape(S, mp))
        self.window = init_kv_window(self.cache, 3 * width) \
            if windowed else None
        self.wlen = jnp.zeros((S,), jnp.int32) if windowed else None
        self.state = init_ssm_state(cfg, S)
        self.steps, self.loads = 0, []

    def flush(self):
        if self.window is not None:
            self.cache, self.wlen, _ = flush_paged_window(
                self.cache, self.window, self.wlen)

    def restart(self, slot):
        """A slot's next tenant: what Scheduler._seed_mixed_slot edits
        (lengths and staged count at zero) and nothing of the state."""
        self.flush()
        self.cache = self.cache._replace(
            lengths=self.cache.lengths.at[slot].set(0))

    def step(self, decode: dict, chunk=None):
        """decode {slot: token}; chunk (slot, tokens up to C) or None.
        Returns {slot: logits [V]} of the rows the head read."""
        S = self.cache.num_slots
        if self.steps % 3 == 0:
            self.flush()
        self.steps += 1
        toks, active = np.zeros((S,), np.int32), np.zeros((S,), bool)
        for s, t in decode.items():
            toks[s], active[s] = t, True
        ctok = np.zeros((1 + self.idle, self.C), np.int32)
        cslot, count = 0, 0
        if chunk is not None:
            cslot, count = chunk[0], len(chunk[1])
            ctok[0, :count] = chunk[1]
        rest = [0] * self.idle
        # a model without recurrent layers returns no state
        logits, kv, load, *st = _packed_step(
            self.params, self.cfg, jnp.asarray(toks), self.cache,
            jnp.asarray(ctok), jnp.asarray([cslot] + rest),
            jnp.asarray([count] + rest),
            jnp.asarray(active), self.window, self.wlen, state=self.state,
            use_kernel=self.use_kernel)
        self.state = st[0] if st else None
        adv = jnp.asarray(active, jnp.int32).at[cslot].add(count)
        if self.window is not None:
            self.window, self.wlen = kv, self.wlen + adv
        else:
            self.cache = kv._replace(lengths=self.cache.lengths + adv)
        self.loads.append(np.asarray(load))
        heads = dict(decode)
        if count:
            heads[cslot] = None
        return {s: np.asarray(logits[s]) for s in heads}


def lane_wide_chunk(eng, slot, tokens):
    """One chunk of one slot's prompt through a ServingEngine's pool by
    the lane-wide forward (cache/paged.py paged_forward: every other
    slot inactive), at the slot's written length. Returns the chunk's
    last-position logits [V]; the engine's cache holds the chunk."""
    eng._sync_table()
    buf = np.zeros((eng.num_slots, len(tokens)), np.int32)
    buf[slot] = tokens
    logits, eng.cache = paged_forward(
        eng.params, eng.cfg, jnp.asarray(buf), eng.cache,
        active=jnp.arange(eng.num_slots) == slot)
    return logits[slot, -1]


def idle_chunk_run(params, seq, cfg, idle=1, **driver):
    """Slot 0 takes seq's first 17 tokens as chunks of 6 (6, 6, 5) and
    decodes two more while slot 1 decodes seq beside it from the first
    step, every step carrying `idle` more chunks that are IDLE (their
    slot reads 0, the slot the real chunk writes). Returns ([(slot,
    position, logits)], the driver)."""
    drv, out = Packed(params, cfg, idle=idle, **driver), []
    for t, lo in enumerate((0, 6, 12)):
        got = drv.step({1: seq[t]}, (0, seq[lo:min(lo + 6, 17)]))
        out += [(0, min(lo + 6, 17) - 1, got[0]), (1, t, got[1])]
    for t in (17, 18):
        got = drv.step({0: seq[t], 1: seq[t - 14]})
        out += [(0, t, got[0]), (1, t - 14, got[1])]
    return out, drv


def scripted_run(params, tokens, cfg, windowed=True, use_kernel=False,
                 **driver):
    """Slot 1 takes sequence 1's first 20 tokens in chunks of 6 (the
    last holds 2 and 4 of filler) and decodes to position 30 while slot
    0 takes sequence 0's first 15 (6, 6, 3) and decodes beside it; then
    slot 1's stream ends and the slot is given to sequence 2 from
    position 0 while slot 0 decodes on. Slot 2 never holds a stream.
    Returns ([(sequence, position, logits)], the driver, {slot:
    (sequence, tokens it has seen)})."""
    drv, out = Packed(params, cfg, windowed, use_kernel=use_kernel,
                      **driver), []
    at = {0: 0, 1: 0}                       # positions fed, by slot
    seq = {0: 0, 1: 1}

    def feed(decode_slots, chunk_slot=None, n=0):
        decode = {s: tokens[seq[s], at[s]] for s in decode_slots}
        chunk = None if chunk_slot is None else (
            chunk_slot, tokens[seq[chunk_slot],
                               at[chunk_slot]:at[chunk_slot] + n])
        got = drv.step(decode, chunk)
        for s in decode_slots:
            at[s] += 1
        if chunk_slot is not None:
            at[chunk_slot] += n
        out.extend((seq[s], at[s] - 1, row) for s, row in got.items())

    for n in (6, 6, 6, 2):
        feed([], 1, n)
    for n in (6, 6, 3):
        feed([1], 0, n)
    while at[1] < 30:
        feed([0, 1])
    drv.restart(1)
    seq[1], at[1] = 2, 0
    for n in (6, 6, 5):
        feed([0], 1, n)
    for _ in range(4):
        feed([0, 1])
    return out, drv, {s: (seq[s], at[s]) for s in at}


def swapped_tails(hlo: str, conv, rows: int, copies=()) -> list:
    """The instructions of a compiled mixed step's text that hold a
    value of the conv's tails in any shape but the stored one (PR 64):
    a slot's tail [S, K-1, Dc] or [S (K-1), Dc], a tail joined with its
    row's input [S, K, Dc] (what the transpose, the concatenate and the
    gather of PR 63's decode rows made of a layer's planes), the whole
    conv [Ls, K-1, S, Dc] or a layer of it laid out slots-major (what
    XLA made of the new planes STACKED, at granite's widths), or a
    value of a row a slot and Dc wide that sits a row a tile
    (`T(1,128)`: PERF.md, PR 53); and of `copies` (tools/chip_kernels.py
    state_copies of the tails) all but a move between memories (a toy's
    few layers of tails the compiler may move whole into its fast
    memory and back, once a step: no cell's tails fit there). conv: the
    state's tails (a shape will do); rows: the step's rows, S + P C."""
    import re
    Ls, K1, S, Dc = conv.shape
    swapped = re.compile(
        rf"^\s*(?:ROOT )?%\S+ = \(?\w+\[(?:{S},(?:{K1}|{K1 + 1})|{S * K1}),"
        rf"{Dc}\]"
        rf"|= \(?\w+\[(?:{Ls}|1),{K1},{S},{Dc}\]\{{3,1,2,0"
        rf"|= \(?\w+\[(?:{S}|{rows}),{Dc}\]\{{[^}}]*T\(1,128\)")
    return [line.strip()[:160] for line in hlo.splitlines()
            if swapped.search(line)] \
        + [c for c in copies if not c.startswith(("copy-start", "copy-done"))]


def planes_unread(hlo: str, conv, mixers) -> list:
    """The operations of a compiled mixed step's text whose result
    carries the conv's planes (the whole conv [Ls, K-1, S, Dc], or
    layer m's [K-1, S, Dc]) that the cell's reader of its mixers
    (`mixers`: servebench's compiled *_patterns) does NOT catch under
    the name servebench/xplane.py gives an operation: the benchmark's
    shares of the mixers must go on counting the conv. A parameter, a
    tuple's element, a bitcast, a loop and a move between memories make
    no operation of a device trace."""
    import re

    from servebench.xplane import clean
    Ls, K1, S, Dc = conv.shape
    made = re.compile(
        rf"^\s*(?:ROOT )?%\S+ = \(?[^=]*\w+\[(?:{Ls},|1,)?{K1},{S},{Dc}\]"
        rf"[^=]* (?!parameter|get-tuple-element|bitcast|tuple|while|copy-"
        rf"|slice-|custom-call\(%slice)[\w-]+\(")
    fused = set(re.findall(r" fusion\(.*?calls=%([^\s,)]+)", hlo))
    head = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
    missed, inside = [], False
    for line in hlo.splitlines():
        m = head.match(line)
        if m:
            inside = m.group(1) not in fused
        elif inside and made.match(line) \
                and not mixers.search(clean(line.strip())):
            missed.append(line.strip()[:160])
    return missed
