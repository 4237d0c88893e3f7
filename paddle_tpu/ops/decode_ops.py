"""Sequence decode / structured prediction ops: CTC, CRF, edit distance,
chunk evaluation, beam search
(ref: operators/warpctc_op.cc, ctc_align_op.cc, edit_distance_op.cc,
linear_chain_crf_op.cc/.h, crf_decoding_op.cc, chunk_eval_op.cc,
beam_search_op.cc, beam_search_decode_op.cc).

TPU-native designs:
- warpctc → the standard log-space CTC recursion (optax.ctc_loss) over
  lod-padded [B, T, C]; fully differentiable, so backward needs no
  WarpCTCGrad plumbing.
- CRF forward/viterbi → one lax.scan per direction over padded time with
  masks; transition layout follows the reference exactly (row 0 = start,
  row 1 = end, rows 2.. = D x D — linear_chain_crf_op.h:150-151), output is
  the negative log-likelihood (linear_chain_crf_op.h:192 `return -ll`).
- Decoders (ctc_greedy, viterbi path, beam search) keep STATIC shapes: a
  decoded sequence is left-aligned in its original-lod row span, padded
  with -1 (greedy) / end_id (beam). The reference emits data-dependent
  LoDs — dynamic shapes XLA cannot compile; -1/end padding carries the
  same information and edit_distance/chunk_eval below understand it.
- beam_search uses a FIXED beam width K: finished beams propagate end_id
  with frozen scores instead of shrinking the beam (the reference prunes
  via LoD). This is the standard TPU beam search formulation.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.registry import register
from ..framework import int_t as INT_T
from ..core.lod import LoDArray, unwrap, lengths_to_offsets
from .rnn_ops import _pad_from_lod


def _lod_offsets(x, what):
    if not (isinstance(x, LoDArray) and x.lod):
        raise TypeError("%s requires a LoD input" % what)
    return np.asarray(x.lod[-1], np.int64)


def _pad_batch(x, what):
    """LoDArray -> (padded [B, T, ...], mask [B, T], offsets)."""
    off = _lod_offsets(x, what)
    padded, mask = _pad_from_lod(unwrap(x), off)
    return padded, mask, off


# ---------------------------------------------------------------------------
# CTC
# ---------------------------------------------------------------------------

@register('warpctc', lod='aware')
def _warpctc(ctx, ins):
    import optax
    logits = ins['Logits'][0]
    label = ins['Label'][0]
    blank = int(ctx.attr('blank', 0))
    norm_by_times = bool(ctx.attr('norm_by_times', False))

    lg, lg_mask, lg_off = _pad_batch(logits, 'warpctc Logits')
    lb, lb_mask, _ = _pad_batch(label, 'warpctc Label')
    lb = lb.reshape(lb.shape[0], -1).astype(jnp.int32)

    # optax paddings: 1.0 where padded
    logit_pad = 1.0 - lg_mask.astype(lg.dtype)
    label_pad = 1.0 - lb_mask.astype(lg.dtype)
    if blank != 0:
        # optax fixes blank_id=0: rotate classes so `blank` sits at 0
        perm = [blank] + [c for c in range(lg.shape[-1]) if c != blank]
        lg = lg[..., jnp.asarray(perm)]
        inv = np.argsort(perm)
        lb = jnp.asarray(inv)[lb]
    loss = optax.ctc_loss(lg, logit_pad, lb, label_pad)  # [B]
    if norm_by_times:
        # reference normalizes only the GRADIENT by sequence length
        # (WarpCTCGradKernel / UnpaddingLoDTensorFunctor) while reporting
        # the unnormalized loss value; value-preserving stop_gradient trick
        lens = jnp.asarray((lg_off[1:] - lg_off[:-1]).astype(np.float32))
        scaled = loss / lens
        loss = scaled + jax.lax.stop_gradient(loss - scaled)
    return {'Loss': [loss.reshape(-1, 1)], 'WarpCTCGrad': None}


def _align_flat(best, off, blank, merge_repeated=True):
    """Merge repeats (optionally) and drop blanks over a flat LoD token
    stream; kept tokens left-align within their original row span, -1
    elsewhere (see module docstring on static shapes). One program
    regardless of batch: a frame is kept if it differs from the previous
    frame OF THE SAME SEQUENCE (when merging) and is not blank; kept
    tokens scatter to their within-sequence rank."""
    T = best.shape[0]
    lens = off[1:] - off[:-1]
    seg = jnp.asarray(np.repeat(np.arange(len(lens)), lens).astype(np.int32))
    off_j = jnp.asarray(off.astype(np.int32))
    prev = jnp.concatenate([jnp.full((1,), -1, best.dtype), best[:-1]])
    first = jnp.asarray(
        np.isin(np.arange(T), off[:-1]))  # first frame of each sequence
    fresh = (first | (best != prev)) if merge_repeated \
        else jnp.ones((T,), bool)
    keep = fresh & (best != blank)
    csum = jnp.cumsum(keep.astype(jnp.int32))
    seq_base = jnp.take(jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), csum]), jnp.take(off_j, seg))
    rank = csum - 1 - seq_base                    # within-seq kept rank
    tgt = jnp.where(keep, jnp.take(off_j, seg) + rank, T)
    return jnp.full((T,), -1, best.dtype).at[tgt].set(best, mode='drop')


@register('ctc_greedy_decoder', no_grad=True, lod='aware')
def _ctc_greedy_decoder(ctx, ins):
    """Best-path decode: argmax per frame, merge repeats, drop blanks.
    Output keeps the input lod; decoded tokens are left-aligned per row
    span, -1 elsewhere."""
    x = ins['Input'][0]
    blank = int(ctx.attr('blank', 0))
    off = _lod_offsets(x, 'ctc_greedy_decoder')
    best = jnp.argmax(unwrap(x), axis=-1).astype(INT_T())  # [T]
    out = _align_flat(best, off, blank)
    return {'Output': [LoDArray(out.reshape(-1, 1), x.lod)]}


@register('ctc_align', no_grad=True, lod='aware')
def _ctc_align(ctx, ins):
    """CTC alignment over already-decoded token ids: optionally merge
    repeats, always remove blanks (ref: operators/ctc_align_op.cc). Unlike
    the reference (which compacts the LoD), output keeps the input lod
    with -1 padding after each sequence's kept tokens — the framework's
    static-shape policy (module docstring)."""
    x = ins['Input'][0]
    blank = int(ctx.attr('blank', 0))
    merge = bool(ctx.attr('merge_repeated', True))
    off = _lod_offsets(x, 'ctc_align')
    toks = unwrap(x).reshape(-1).astype(INT_T())
    out = _align_flat(toks, off, blank, merge_repeated=merge)
    return {'Output': [LoDArray(out.reshape(-1, 1), x.lod)]}


@register('edit_distance', no_grad=True, lod='aware')
def _edit_distance(ctx, ins):
    """Levenshtein distance per sequence pair. Accepts LoD rows, optionally
    -1-padded (ctc_greedy_decoder output): -1 entries don't count as
    tokens. DP over the padded grid via nested lax.scan; the answer is
    gathered at the (possibly traced) true lengths."""
    hyps, refs = ins['Hyps'][0], ins['Refs'][0]
    normalized = bool(ctx.attr('normalized', True))
    ignored = tuple(ctx.attr('ignored_tokens', ()) or ())
    h_off = _lod_offsets(hyps, 'edit_distance Hyps')
    r_off = _lod_offsets(refs, 'edit_distance Refs')
    h = unwrap(hyps).reshape(-1).astype(INT_T())
    r = unwrap(refs).reshape(-1).astype(INT_T())
    n = len(h_off) - 1

    def compact(seq):
        """Left-align valid tokens (drop -1 pads and ignored tokens), -1
        padding after — interior holes would otherwise count in the DP."""
        keep = seq >= 0
        for tok in ignored:
            keep &= seq != tok
        pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
        L = seq.shape[0]
        tgt = jnp.where(keep, pos, L)
        return jnp.full((L,), -1, seq.dtype).at[tgt].set(seq, mode='drop')

    def one_pair(hseq, rseq):
        """hseq [maxH], rseq [maxR]; -1 = pad. Returns distance."""
        hlen = jnp.sum(hseq >= 0).astype(jnp.int32)
        rlen = jnp.sum(rseq >= 0).astype(jnp.int32)
        max_r = rseq.shape[0]
        row0 = jnp.arange(max_r + 1, dtype=jnp.int32)

        def row_step(prev_row, hi):
            first = prev_row[0] + 1

            def col_step(left, inp):
                up, diag, rj = inp
                cost = jnp.where(hi == rj, 0, 1).astype(jnp.int32)
                new = jnp.minimum(jnp.minimum(up + 1, left + 1), diag + cost)
                return new, new

            _, rest = jax.lax.scan(
                col_step, first, (prev_row[1:], prev_row[:-1], rseq))
            new_row = jnp.concatenate([first[None], rest])
            return new_row, new_row

        _, rows = jax.lax.scan(row_step, row0, hseq)
        all_rows = jnp.concatenate([row0[None], rows], axis=0)
        return all_rows[hlen, rlen].astype(jnp.float32)

    # batch the pairs: lod-pad to [B, maxH]/[B, maxR] (-1 beyond each
    # sequence) and vmap the DP — program size is O(1) in the batch
    from .rnn_ops import _pad_from_lod
    hp, hm = _pad_from_lod(h, h_off)
    rp, rm = _pad_from_lod(r, r_off)
    hp = jnp.where(hm, hp, -1)
    rp = jnp.where(rm, rp, -1)
    hseq = jax.vmap(compact)(hp)
    rseq = jax.vmap(compact)(rp)
    d = jax.vmap(one_pair)(hseq, rseq)
    if normalized:
        rlen = jnp.maximum(jnp.sum(rseq >= 0, axis=1), 1)
        d = d / rlen.astype(jnp.float32)
    return {'Out': [d.reshape(-1, 1)],
            'SequenceNum': [jnp.asarray(n, INT_T()).reshape(1)]}


# ---------------------------------------------------------------------------
# CRF
# ---------------------------------------------------------------------------

def _split_transition(w):
    """Reference layout (linear_chain_crf_op.h:150): row0 start, row1 end,
    rows 2.. the D x D transition matrix."""
    return w[0], w[1], w[2:]


@register('linear_chain_crf', lod='aware')
def _linear_chain_crf(ctx, ins):
    em = ins['Emission'][0]
    w = unwrap(ins['Transition'][0])
    label = ins['Label'][0]
    start, end, trans = _split_transition(w)

    E, mask, off = _pad_batch(em, 'linear_chain_crf Emission')   # [B,T,D]
    y = _pad_batch(label, 'linear_chain_crf Label')[0]
    y = y.reshape(y.shape[0], -1).astype(jnp.int32)              # [B,T]
    B, T, D = E.shape
    lens = jnp.asarray((off[1:] - off[:-1]).astype(np.int32))

    Et = jnp.moveaxis(E, 1, 0)       # [T,B,D]
    mt = jnp.moveaxis(mask, 1, 0)    # [T,B]
    yt = jnp.moveaxis(y, 1, 0)       # [T,B]

    # ---- log partition: masked forward recursion --------------------------
    alpha0 = start[None, :] + Et[0]                              # [B,D]

    def fwd(alpha, inp):
        e_t, m_t = inp
        nxt = jax.nn.logsumexp(alpha[:, :, None] + trans[None], axis=1) + e_t
        alpha = jnp.where(m_t[:, None], nxt, alpha)
        return alpha, None

    alphaT, _ = jax.lax.scan(fwd, alpha0, (Et[1:], mt[1:]))
    logZ = jax.nn.logsumexp(alphaT + end[None, :], axis=1)       # [B]

    # ---- gold path score --------------------------------------------------
    brange = jnp.arange(B)
    gold = start[yt[0]] + Et[0][brange, yt[0]]

    def gstep(g, inp):
        e_t, m_t, y_prev, y_t = inp
        step = trans[y_prev, y_t] + e_t[brange, y_t]
        return g + jnp.where(m_t, step, 0.0), None

    gold, _ = jax.lax.scan(gstep, gold, (Et[1:], mt[1:], yt[:-1], yt[1:]))
    y_last = y[brange, lens - 1]
    gold = gold + end[y_last]

    nll = (logZ - gold).reshape(-1, 1)   # reference returns -loglik
    zeros = jnp.zeros(unwrap(em).shape, unwrap(em).dtype)
    return {'LogLikelihood': [nll],
            'Alpha': [zeros], 'EmissionExps': [zeros],
            'TransitionExps': [jnp.zeros_like(w)]}


@register('crf_decoding', no_grad=True, lod='aware')
def _crf_decoding(ctx, ins):
    em = ins['Emission'][0]
    w = unwrap(ins['Transition'][0])
    label = ins['Label'][0] if ins.get('Label') and ins['Label'][0] is not None \
        else None
    start, end, trans = _split_transition(w)

    E, mask, off = _pad_batch(em, 'crf_decoding Emission')
    B, T, D = E.shape
    lens = np.asarray(off[1:] - off[:-1], np.int64)
    Et = jnp.moveaxis(E, 1, 0)
    mt = jnp.moveaxis(mask, 1, 0)

    # viterbi forward with backpointers; freeze finished rows via mask
    d0 = start[None, :] + Et[0]

    def vstep(delta, inp):
        e_t, m_t = inp
        cand = delta[:, :, None] + trans[None]          # [B,D,D]
        best = jnp.max(cand, axis=1) + e_t
        bp = jnp.argmax(cand, axis=1).astype(jnp.int32)
        new = jnp.where(m_t[:, None], best, delta)
        bp = jnp.where(m_t[:, None], bp,
                       jnp.arange(D, dtype=jnp.int32)[None, :])
        return new, (bp, new)

    _, (bps, deltas) = jax.lax.scan(vstep, d0, (Et[1:], mt[1:]))
    deltas = jnp.concatenate([d0[None], deltas], axis=0)      # [T,B,D]

    # each sequence ends at its static length: read delta there
    brange = jnp.arange(B)
    last_idx = jnp.asarray(lens - 1, jnp.int32)
    final = deltas[last_idx, brange] + end[None, :]
    tags_last = jnp.argmax(final, axis=1).astype(jnp.int32)   # [B]

    # backtrace (reverse scan over backpointers, frozen past seq end);
    # bps[t] connects steps t and t+1, valid where mask[t+1]
    def back(tag, inp):
        bp, m_t = inp
        prev = bp[brange, tag]
        prev = jnp.where(m_t, prev, tag)
        return prev, tag

    tag0, tail_rev = jax.lax.scan(back, tags_last,
                                  (bps[::-1], mt[1:][::-1]))
    # tail_rev holds tags at steps T-1..1; prepend the step-0 carry
    path = jnp.concatenate([tag0[None], tail_rev[::-1]], axis=0)  # [T,B]
    path = jnp.moveaxis(path, 1, 0).astype(INT_T())             # [B,T]

    from .rnn_ops import _unpad_to_lod
    off_b = np.concatenate([[0], np.cumsum(lens)])
    flat = _unpad_to_lod(path[..., None], off_b).reshape(-1, 1)
    if label is not None:
        lab = unwrap(label).reshape(-1, 1).astype(INT_T())
        flat = (flat == lab).astype(INT_T())
    return {'ViterbiPath': [LoDArray(flat, em.lod)]}


# ---------------------------------------------------------------------------
# chunk_eval (ref operators/chunk_eval_op.cc): precision/recall/F1 of chunk
# labeling. Tag encoding for scheme IOB: tag = chunk_type * num_tag_types +
# tag_type, tag_type 0 = B, 1 = I. 'plain': every tag is its own chunk type.
# ---------------------------------------------------------------------------

def _chunk_bounds(tags, scheme, num_chunk_types, excluded):
    """tags [L] int; returns (is_start [L], is_end [L], ctype [L], valid)."""
    L = tags.shape[0]
    if scheme == 'plain':
        ctype = tags
        # the 'Other' tag decodes to type == num_chunk_types and is never a
        # chunk (ref chunk_eval_op.h:145 other_chunk_type)
        valid = (tags >= 0) & (tags != num_chunk_types)
        for e in excluded:
            valid &= tags != e
        prev = jnp.concatenate([jnp.full((1,), -2, tags.dtype), tags[:-1]])
        nxt = jnp.concatenate([tags[1:], jnp.full((1,), -2, tags.dtype)])
        is_start = valid & (prev != tags)
        is_end = valid & (nxt != tags)
        return is_start, is_end, ctype, valid
    # positional schemes (ref chunk_eval_op.h:118-136 GetSegments): tag =
    # chunk_type * num_tag_types + tag_type; per-scheme tag-type codes
    # (absent roles are None, dropping their predicate terms):
    #   IOB   — B=0 I=1            IOE   — I=0 E=1
    #   IOBES — B=0 I=1 E=2 S=3
    try:
        B, I, E, S, ntt = {'IOB': (0, 1, None, None, 2),
                           'IOE': (None, 0, 1, None, 2),
                           'IOBES': (0, 1, 2, 3, 4)}[scheme]
    except KeyError:
        raise NotImplementedError("chunk_eval scheme %r (supported: plain, "
                                  "IOB, IOE, IOBES)" % scheme)
    ttype = tags % ntt
    ctype = tags // ntt
    # O tags (value num_chunk_types * num_tag_types) decode to
    # ctype == num_chunk_types: not part of any chunk (ref chunk_eval_op.h:145)
    valid = (tags >= 0) & (ctype != num_chunk_types)
    for e in excluded:
        valid &= ctype != e
    prev_ct = jnp.concatenate([jnp.full((1,), -2, ctype.dtype), ctype[:-1]])
    prev_tt = jnp.concatenate([jnp.full((1,), -2, ttype.dtype), ttype[:-1]])
    prev_valid = jnp.concatenate([jnp.zeros((1,), bool), valid[:-1]])
    nxt_ct = jnp.concatenate([ctype[1:], jnp.full((1,), -2, ctype.dtype)])
    nxt_tt = jnp.concatenate([ttype[1:], jnp.full((1,), -2, ttype.dtype)])
    nxt_valid = jnp.concatenate([valid[1:], jnp.zeros((1,), bool)])
    # a chunk starts at t when the chunk run cannot continue through t:
    # no valid predecessor / type switch, an explicit B/S tag here, or the
    # predecessor closed its chunk (E/S). Symmetrically for ends.
    is_start = ~prev_valid | (prev_ct != ctype)
    is_end = ~nxt_valid | (nxt_ct != ctype)
    if B is not None:
        is_start |= ttype == B
        is_end |= nxt_tt == B
    if S is not None:
        is_start |= (ttype == S) | (prev_tt == S)
        is_end |= (ttype == S) | (nxt_tt == S)
    if E is not None:
        is_start |= prev_tt == E
        is_end |= ttype == E
    return valid & is_start, valid & is_end, ctype, valid


@register('chunk_eval', no_grad=True, lod='aware')
def _chunk_eval(ctx, ins):
    inf = ins['Inference'][0]
    lab = ins['Label'][0]
    scheme = ctx.attr('chunk_scheme', 'IOB')
    num_chunk_types = int(ctx.attr('num_chunk_types', 1))
    excluded = tuple(ctx.attr('excluded_chunk_types', ()) or ())
    off = _lod_offsets(lab, 'chunk_eval Label')

    iv = unwrap(inf).reshape(-1).astype(jnp.int32)
    lv = unwrap(lab).reshape(-1).astype(jnp.int32)

    n_inf = jnp.zeros((), jnp.int32)
    n_lab = jnp.zeros((), jnp.int32)
    n_cor = jnp.zeros((), jnp.int32)
    for s in range(len(off) - 1):
        i_seg = iv[int(off[s]):int(off[s + 1])]
        l_seg = lv[int(off[s]):int(off[s + 1])]
        i_st, i_en, i_ct, _ = _chunk_bounds(i_seg, scheme, num_chunk_types,
                                            excluded)
        l_st, l_en, l_ct, _ = _chunk_bounds(l_seg, scheme, num_chunk_types,
                                            excluded)
        n_inf += jnp.sum(i_st)
        n_lab += jnp.sum(l_st)
        # a chunk is correct if start/end/type AND the span agree; spans
        # agree iff the end positions for the start both coincide — check:
        # both start at p, same type, and for the region until the shared
        # end, ends match. Count starts where (start match & type match &
        # the next end matches): next-end index via running min of end pos.
        L = i_seg.shape[0]
        idx = jnp.arange(L)
        big = L + 1

        def next_end(is_end):
            pos = jnp.where(is_end, idx, big)
            return jax.lax.associative_scan(jnp.minimum, pos[::-1])[::-1]

        both_start = i_st & l_st & (i_ct == l_ct)
        n_cor += jnp.sum(both_start & (next_end(i_en) == next_end(l_en)))

    n_inf_f = n_inf.astype(jnp.float32)
    n_lab_f = n_lab.astype(jnp.float32)
    n_cor_f = n_cor.astype(jnp.float32)
    prec = jnp.where(n_inf > 0, n_cor_f / n_inf_f, 0.0).reshape(1)
    rec = jnp.where(n_lab > 0, n_cor_f / n_lab_f, 0.0).reshape(1)
    f1 = jnp.where(n_cor > 0, 2 * prec * rec / (prec + rec),
                   jnp.zeros(1)).reshape(1)
    i64 = INT_T()
    return {'Precision': [prec], 'Recall': [rec], 'F1-Score': [f1],
            'NumInferChunks': [n_inf.astype(i64).reshape(1)],
            'NumLabelChunks': [n_lab.astype(i64).reshape(1)],
            'NumCorrectChunks': [n_cor.astype(i64).reshape(1)]}


# ---------------------------------------------------------------------------
# KV-cache decode steps (continuous in-flight batching, ISSUE 8)
#
# The decode-serving tier (inference/decoding.py) runs autoregressive
# models as fixed-shape programs over a preallocated block-paged KV pool
# held as persistable state: chunked-PREFILL programs write one slice of
# one prompt's K/V rows through its slot's block table, and a
# DECODE-STEP program advances every slot by one token. The kv_block_*
# ops below are the cache-aware primitives those programs use; the
# bodies here are the masked-attention expressions they share, over a
# slot's gathered logical view [T', D]. Per-slot math never mixes rows,
# so a slot's outputs are bit-identical regardless of which other
# requests co-reside in the batch — the continuous-batching determinism
# contract.
# ---------------------------------------------------------------------------

def _head_attrs(ctx, d):
    """(n_head, n_kv_head, d_head, scale, window) of an attention op over
    a cache of width d = n_kv_head * d_head: attr n_kv_head (default:
    n_head) K/V heads, query head h reading K/V head h // (n_head //
    n_kv_head); attr window w > 0 keeps the rows pos - w < j <= pos (0:
    every row j <= pos)."""
    n_head = int(ctx.attr('n_head', 1))
    n_kv = int(ctx.attr('n_kv_head', 0) or 0) or n_head
    if n_head % n_kv:
        raise ValueError('n_head %d is not a multiple of n_kv_head %d'
                         % (n_head, n_kv))
    dh = d // n_kv
    scale = float(ctx.attr('scale', 0.0) or 0.0) or dh ** -0.5
    return n_head, n_kv, dh, scale, int(ctx.attr('window', 0) or 0)


def _v_width(ctx, dh):
    """How many of a K/V head's d_head channels are its VALUE: attr
    v_width (default: all of them, and then V is a cache of its own). A
    LATENT pool (one [.., latent + rotary] row a position that every
    query head reads: n_kv_head 1, KCache and VCache the same pool) scores
    over the whole row and sums its first v_width channels — attention in
    the absorbed form, the up-projections folded into the query and the
    output by the program around the op."""
    dv = int(ctx.attr('v_width', 0) or 0)
    if not dv:
        return dh
    if not 0 < dv < dh:
        raise ValueError('v_width %d is not in (0, d_head = %d): a row '
                         'that is all value is a V pool\'s' % (dv, dh))
    # the attribute ALONE says the pool is latent (the bodies and the
    # step's kernel are chosen by it), so what it promises is held here,
    # by name, and not found out from which body a trace happened to take
    n_kv, window = (int(ctx.attr(a, 0) or 0) for a in ('n_kv_head', 'window'))
    if n_kv != 1 or window:
        raise NotImplementedError(
            'a latent pool (attr v_width) has ONE K/V head and no window, '
            'not n_kv_head=%d, window=%d' % (n_kv, window))
    op = getattr(ctx, 'op', None)
    if op is not None and op.input('KCache') != op.input('VCache'):
        raise ValueError(
            'a latent pool (attr v_width) is key and value both: KCache %r '
            'and VCache %r are two variables'
            % (op.input('KCache'), op.input('VCache')))
    return dv


def _in_window(j, pos, window):
    """Which cache rows j a query at position pos attends."""
    valid = j <= pos
    return valid & (j > pos - window) if window else valid


def _paged_attention_body(ctx, q, kc, vc, pos):
    """The shared heads-inside masked attention body: Q [S, n_head *
    d_head] attends its own slot's cache rows j <= pos (inside attr
    window, if one is set: _head_attrs). Used by the fp and the int8-
    dequantizing attention ops — ONE expression, so the fp path's
    bit-identity contract is untouched and the quantized path differs
    only by the dequant of its operands."""
    s, t, d = kc.shape
    n_head, n_kv, dh, scale, window = _head_attrs(ctx, d)
    dv = _v_width(ctx, dh)
    if n_kv != n_head or window or dv != dh:
        g = n_head // n_kv
        qh = q.reshape(s, n_kv, g, dh)
        kh = kc.reshape(s, t, n_kv, dh)
        vh = vc.reshape(s, t, n_kv, dh)
        if dv != dh:
            vh = vh[..., :dv]
        scores = jnp.einsum('skgd,stkd->skgt', qh, kh) * scale
        valid = _in_window(jnp.arange(t, dtype=jnp.int32)[None, :],
                           pos[:, None], window)
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        # with a window the table names pages the slot gave back: their
        # V must not reach the sum even at weight zero (the kernel's rule)
        vh = jnp.where(valid[:, :, None, None], vh, 0)
        ctxv = jnp.einsum('skgt,stkd->skgd', w, vh)
        return ctxv.reshape(s, n_head * dv).astype(q.dtype)
    qh = q.reshape(s, n_head, dh)
    kh = kc.reshape(s, t, n_head, dh)
    vh = vc.reshape(s, t, n_head, dh)
    scores = jnp.einsum('shd,sthd->sht', qh, kh) * scale
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    ctxv = jnp.einsum('sht,sthd->shd', w, vh)
    return ctxv.reshape(s, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# int8-quantized paged KV cache (ISSUE 11): the pool stores int8 rows
# plus ONE f32 scale per cache position — [NB, BS] scales next to the
# [NB, BS, D] int8 pool, ~(1 + 4/D)/2 the bytes of a bf16 cache —
# so a fixed cache-HBM budget holds 2x the slots, the direct
# occupancy -> throughput win for DecodingPredictor. Quantization
# happens at WRITE time (each K/V row is seen exactly once); attention
# dequantizes inside its own body, so no f32 copy of the cache ever
# materializes in HBM.
# ---------------------------------------------------------------------------

_KV_QMAX = 127.0
# an all-zero row quantizes to scale 0; the epsilon keeps q = x/s finite
# (0 / eps = 0) without perturbing any real row's scale
_KV_SCALE_EPS = 1e-30


def _quantize_kv_rows(kv):
    """[..., D] f32 -> (int8 [..., D], f32 scale [...]) with one
    symmetric abs-max scale per row (= per cache position once written)."""
    s = jnp.max(jnp.abs(kv), axis=-1) / _KV_QMAX
    s = jnp.maximum(s, _KV_SCALE_EPS)
    q = jnp.clip(jnp.round(kv / s[..., None]), -_KV_QMAX, _KV_QMAX)
    return q.astype(jnp.int8), s.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Block-paged KV cache (ISSUE 13): the cache is a pool of fixed-size
# blocks [num_blocks, block_size, D] addressed through per-slot BLOCK
# TABLES (int32 [*, max_blocks]): logical position p of a slot lives at
# cache[table[p // bs], p % bs]. Tables are host state the scheduler
# feeds every dispatch (inference/kv_blocks.py owns the refcounts), so
# beam reorder is a table permutation + copy-on-write of the partial
# tail block, and requests with a
# common prompt prefix SHARE the prefix's blocks. Physical block 0 is
# the reserved trash block: idle/padded rows scatter there and no table
# maps it into an attention window, so its (possibly write-racy, but
# never read) bits cannot perturb any active slot — the masked-
# idle-slot determinism contract.
#
# What reads the pool how (ISSUE 25). The plain body of every op here
# gathers a slot's whole logical view [MAXB * BS, D] through _block_view
# and runs the jnp expression above over it, masking rows past pos — on
# those bodies a slot's output is the same BIT FOR BIT however its
# history is paged; the verify and _quant ops have no other. TWO ops
# have a second body that reads the pages a slot has written and no
# more. Where a program is compiled for a TPU, the step's
# kv_block_attention is the Pallas kernel of pallas_paged_attention.py,
# which copies pages 0 .. pos // BS of each slot straight from the pool
# through its table row and never sees a row past pos. And the chunk's
# kv_block_chunk_attention reads pages 0 .. (start + C - 1) //
# _CHUNK_KEY_BLOCK a key block at a time (_chunk_attention_blocked)
# wherever the gathered view is the SLOWER body (_gathered_view_fits,
# ISSUE 56): grouped K/V heads, a window, a latent pool, or scores past
# _CHUNK_SCORES_BYTES — the view costs by max_cache_len whatever the
# slot has written, the blocks by what it has. Same function, float32
# throughout, another summation order (online softmax): such a body
# rounds differently from the gathered view, which stays the reference
# both are tested against. The choice is made from what the lowering
# sees — the platform the program is compiled for, the pool's dtype and
# page shape (ppa.supports), a trace mesh, the chunk's and the view's
# shapes — never from a knob. A LATENT pool (ISSUE 40: attr v_width, K
# and V the same pages, every head reading one row) has a kernel of its
# own (ppa.latent_paged_attention, ppa.refuses_latent) and, in the chunk
# form, always the blocked body. Each of the two ops tells its Tracer
# which body it took (lowered_bodies) and export_decode writes it into
# the signature.
# What WRITES it how (ISSUE 54): the step one row a slot (kv_block_write:
# one index pair a row, nothing to group); a chunk a PAGE at a time where
# C % BS == 0 and it starts on a page (kv_block_chunk_write), else a row.
# ---------------------------------------------------------------------------

def _block_view(cache, table_row):
    """Gather one slot's logically-ordered cache view from the block
    pool: cache [NB, BS, D(+)], table_row [MAXB] int32 ->
    [MAXB * BS, D(+)] (logical row j = position j)."""
    v = jnp.take(cache, table_row, axis=0)       # [MAXB, BS, ...]
    return v.reshape((-1,) + v.shape[2:])


def _block_scatter_idx(table, pos, bs):
    """(physical block, in-block offset) per row: table [R, MAXB], pos
    [R] int32 -> (bidx [R], boff [R]). Rows whose table entry is the
    trash block land at (0, off) — never read. Rows whose position
    overflows the table's logical span (chunked-prefill pad rows past
    max_cache_len) are forced to the trash block too: gather clamping
    would otherwise resolve them to the LAST table column, a real
    block when the table is full."""
    pos = pos.astype(jnp.int32)
    lblk = pos // bs
    boff = pos % bs
    bidx = jnp.take_along_axis(table.astype(jnp.int32),
                               lblk[:, None], axis=1)[:, 0]
    bidx = jnp.where(lblk < table.shape[1], bidx, 0)
    return bidx, boff


@register('sharding_hint', no_grad=True, lod='none')
def _sharding_hint(ctx, ins):
    """GSPMD placement hint: constrain X to the partition spec named by
    attr 'spec' (mesh axis name per dim, '' = replicate that dim; empty
    spec = fully replicated) on the CURRENT TRACE MESH
    (parallel/mesh.trace_mesh_scope — the round-13 pinning machinery).
    Identity when no mesh is in scope, so hinted programs lower
    unchanged on a single chip. The mp-sharded decode programs use
    replicate hints at contraction boundaries: gathering a sharded
    activation BEFORE a matmul contracts over it keeps every reduction
    full-width, which is what makes the sharded transcripts bit-
    identical to the single-chip artifact (partial-sum all-reduces
    reorder the accumulation; all-gathers do not)."""
    x = ins['X'][0]
    from ..parallel.mesh import current_trace_mesh
    mesh = current_trace_mesh()
    if mesh is None:
        return {'Out': [x]}
    from jax.sharding import NamedSharding, PartitionSpec
    spec = tuple((a or None) for a in (ctx.attr('spec', ()) or ()))
    unknown = [a for a in spec if a is not None and a not in mesh.shape]
    if unknown:
        # a silently ignored hint would let GSPMD shard straight through
        # a contraction boundary — partial-sum all-reduces reorder the
        # accumulation and the transcripts drift from single-chip.
        # Fail the trace (= the export) instead.
        raise ValueError(
            'sharding_hint spec %r names axes %r absent from the trace '
            'mesh %r' % (spec, unknown, dict(mesh.shape)))
    return {'Out': [jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))]}


@register('kv_block_write', no_grad=True, lod='none')
def _kv_block_write(ctx, ins):
    """Write one decode step's K or V row per slot into the BLOCK pool:
    Cache [NB, BS, D], KV [S, D], Pos [S] int32, BlockTable [S, MAXB]
    int32. Each slot's row scatters to (table[pos // BS], pos % BS);
    the scheduler guarantees write blocks are uniquely owned (CoW), so
    real scatter indices never collide; idle slots scatter identical
    rows into the trash block. Out aliases Cache (in-place on the
    persistable pool)."""
    cache = ins['Cache'][0]
    kv = ins['KV'][0]
    pos = ins['Pos'][0].reshape(-1)
    table = ins['BlockTable'][0]
    bidx, boff = _block_scatter_idx(table, pos, cache.shape[1])
    return {'Out': [cache.at[bidx, boff].set(kv.astype(cache.dtype))]}


def _kv_block_attention_jnp(ctx, q, kc, vc, pos, table):
    """The reference body: gather each slot's logical view through its
    table row, then _paged_attention_body's masked attention — on this
    body a slot's output is bit-identical however its history is
    paged."""
    kv_view = jax.vmap(lambda r: _block_view(kc, r))(table)  # [S, T', D]
    vv_view = jax.vmap(lambda r: _block_view(vc, r))(table)
    return _paged_attention_body(ctx, q, kv_view, vv_view, pos)


@register('kv_block_attention', no_grad=True, lod='none')
def _kv_block_attention(ctx, ins):
    """One-token-per-slot attention over the block pool: Q [S, n_head *
    d_head], KCache/VCache [NB, BS, D = n_kv_head * d_head], Pos [S]
    int32, BlockTable [S, MAXB] int32; heads split inside the op (attr
    n_head; attr n_kv_head, default n_head, K/V heads each read by
    n_head / n_kv_head query heads). Each slot
    attends its own table's logical view rows j <= pos — with attr
    window w > 0 only pos - w < j <= pos, and then only the pages that
    hold those rows need be the slot's own: the table may name anything
    below them; rows beyond get
    exactly-zero weight, so foreign blocks and trash garbage can never
    perturb an active slot.

    Two bodies (the comment block above). The jnp one — every platform
    but a TPU, and on a TPU a pool the kernel cannot read or a sharded
    trace — is _paged_attention_body over the gathered view. Compiled
    for a TPU, the Pallas
    kernel reads pages 0 .. pos // BS through the table instead.

    Attr v_width (_v_width): a LATENT pool — KCache and VCache the same
    variable, n_kv_head 1, Q n_head rows as wide as the pool's, Out
    n_head * v_width wide. Its kernel is a second one
    (ppa.latent_paged_attention: a page copied once, multiplied twice);
    lowered_bodies says 'latent_kernel' / 'latent_jnp'."""
    from ..parallel.mesh import current_trace_mesh
    from . import pallas_paged_attention as ppa
    q = ins['Q'][0]
    kc = ins['KCache'][0]
    vc = ins['VCache'][0]
    pos = ins['Pos'][0].reshape(-1).astype(jnp.int32)
    table = ins['BlockTable'][0].astype(jnp.int32)
    n_head, n_kv, dh, scale, window = _head_attrs(ctx, kc.shape[2])
    dv = _v_width(ctx, dh)
    jnp_body = functools.partial(_kv_block_attention_jnp, ctx)
    if ctx.abstract:        # shape inference: no Tracer, and any body will do
        return {'Out': [jnp_body(q, kc, vc, pos, table)]}
    if dv == dh:
        body = 'kernel'
        kernel = ppa.supports(q, kc, vc, n_head, n_kv)
        tpu = functools.partial(ppa.paged_attention, n_head=n_head,
                                n_kv_head=n_kv, window=window, scale=scale)
    else:
        # the values lie inside the K rows: ONE pool (_v_width has held
        # the op to it), copied once
        body = 'latent_kernel'
        kernel = ppa.refuses_latent(q, kc, n_head, dv) is None

        def tpu(q, kc, vc, pos, table):
            return ppa.latent_paged_attention(
                q, kc, pos, table, n_head=n_head, v_width=dv, scale=scale)
    kernel = kernel and current_trace_mesh() is None
    ctx.tracer.lowered_bodies.append(
        ('kv_block_attention',
         body if kernel else body.replace('kernel', 'jnp')))
    if not kernel:
        return {'Out': [jnp_body(q, kc, vc, pos, table)]}
    return {'Out': [ppa.tpu_or_default(
        q, kc, vc, pos, table, default=jnp_body, tpu=tpu,
        out_width=None if dv == dh else n_head * dv)]}


def _chunk_attention_body(ctx, q, kview, vview, start, d):
    """Chunked-prefill attention for ONE slot: q [1, C, D] (chunk rows
    at absolute positions start + i), kview/vview [T', D] the slot's
    logical cache view. Row i attends j <= start + i — causal within the
    chunk AND over every previously written position (earlier chunks,
    shared prefix blocks). Heads inside (attr n_head: as many K/V heads
    as query heads, and no window — _chunk_attention_blocked has
    those); exactly-zero masked weights (the step op's contract)."""
    n_head, n_kv, dh, scale, window = _head_attrs(ctx, d)
    if n_kv != n_head or window:
        raise NotImplementedError(
            'chunk attention over a gathered view has neither grouped '
            'K/V heads (n_kv_head=%d of n_head=%d) nor a window (%d)'
            % (n_kv, n_head, window))
    c = q.shape[1]
    t = kview.shape[0]
    qh = q.reshape(c, n_head, dh)
    kh = kview.reshape(t, n_head, dh)
    vh = vview.reshape(t, n_head, dh)
    scores = jnp.einsum('chd,thd->cht', qh, kh) * scale
    start = start.reshape(()).astype(jnp.int32)
    valid = (jnp.arange(t, dtype=jnp.int32)[None, :]
             <= start + jnp.arange(c, dtype=jnp.int32)[:, None])
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    ctxv = jnp.einsum('cht,thd->chd', w, vh)
    return ctxv.reshape(1, c, d).astype(q.dtype)


# What the chunk op decides from the shapes it is given
# (_kv_block_chunk_attention): the gathered view is taken while the
# float32 [C, n_head, T'] scores of all its R rows together are this
# many bytes or fewer — the size above which it is the slower body, not
# a memory guard (ISSUE 56, a v5e, six layers: at 128 MiB — C 512, 16
# heads, 4,096 positions — the view takes 3.59 ms whatever `start` is
# and the blocks 0.55 - 1.93 by `start`; at 32 MiB and under the view is
# level at `start` 0 and ahead from the second key block on) — and the
# blocked body reads this many positions at a time.
_CHUNK_SCORES_BYTES = 64 << 20
_CHUNK_KEY_BLOCK = 512
# The ONE row program of a decode spec (chunk_row_program): a dispatch
# carries at most this many prompt tokens — the largest chunk of the MoE
# configurations, i.e. how much prefill may sit in front of the other
# streams' next token — in at most this many rows (slices of DIFFERENT
# admitting requests). PERF.md section 6 (PR 39) has the chip's reading
# of a 4-row call's host and device time.
_CHUNK_ROW_TOKENS = 512
_CHUNK_ROWS = 4


def _gathered_view_fits(rows, c, n_head, n_kv, window, view_len):
    """Whether `rows` chunk rows of c positions take the gathered-view
    body (_chunk_attention_body): as many K/V heads as query heads,
    nothing windowed, and the rows' float32 [c, n_head, view_len] scores
    within _CHUNK_SCORES_BYTES together — past it the view, which costs
    by view_len whatever the slot has written, is slower than the blocks
    that hold what it has."""
    return (n_kv == n_head and not window
            and 4 * rows * c * n_head * view_len <= _CHUNK_SCORES_BYTES)


def _one_row_only(op, x):
    """The chunk forms that keep R = 1 (the int8 pool's) say so by name
    when handed more."""
    if x.shape[0] != 1:
        raise NotImplementedError(
            '%s takes one chunk row, got %d: only kv_block_chunk_write / '
            'kv_block_chunk_attention over the gathered view have a row '
            'dimension' % (op, x.shape[0]))


def chunk_row_program(chunk_sizes, attentions, view_len):
    """(C, R) of the one chunked-prefill program a decode spec builds
    with a leading ROW dimension — R slices of different prompts in one
    dispatch — or None. From shapes alone: C is the spec's largest
    chunk, R = min(_CHUNK_ROWS, _CHUNK_ROW_TOKENS // C); there is one
    where R > 1 and every attention of the chunk program — `attentions`:
    (op type, n_head, n_kv_head, window) of each — is a
    kv_block_chunk_attention that takes its gathered-view body at
    [R, C] over a view of `view_len` positions. The int8 pool's _quant
    form, grouped heads and a window keep R = 1."""
    c = max(int(x) for x in chunk_sizes)
    rows = min(_CHUNK_ROWS, _CHUNK_ROW_TOKENS // c)
    if rows > 1 and attentions and all(
            op == 'kv_block_chunk_attention'
            and _gathered_view_fits(rows, c, n_head, n_kv or n_head,
                                    window, view_len)
            for op, n_head, n_kv, window in attentions):
        return c, rows
    return None


def _chunk_attention_blocked(ctx, q, kc, vc, start, table):
    """_chunk_attention_body's function — with grouped K/V heads and a
    window (_head_attrs) — without its [C, n_head, T'] scores: the
    slot's pages are gathered _CHUNK_KEY_BLOCK positions at a time, from
    the block that holds the first row any chunk row attends (position
    0, or start - window + 1) to the one that holds start + C - 1, under
    an online softmax — what a long cache needs (at C = 512, 64 heads
    and 12,288 positions the whole scores are 1.6 GB of float32). Both
    products at float32 'highest', as the step's kernel has them. A row
    with nothing to attend (a pad row further past the cache's end than
    the window is long) gives zeros."""
    bs, d = kc.shape[1], kc.shape[2]
    n_head, n_kv, dh, scale, window = _head_attrs(ctx, d)
    dv = _v_width(ctx, dh)
    c = q.shape[1]
    pages = min(max(_CHUNK_KEY_BLOCK // bs, 1), table.shape[0])
    key_block = pages * bs
    g = n_head // n_kv
    high = jax.lax.Precision.HIGHEST
    # what the products multiply: float32 copies at 'highest'; over a
    # latent pool (v_width: every head reads the whole 4.5-tile row, 4.5
    # times a K/V row's products) the pool's own dtype, as the step's
    # latent kernel has them — bfloat16 x bfloat16 with float32 sums
    # where the pool is bfloat16, one MXU pass and not six
    mult = jnp.float32 if dv == dh else kc.dtype
    if mult != jnp.float32:
        high = None
    qh = q.reshape(c, n_kv, g, dh).astype(mult)
    start = start.reshape(()).astype(jnp.int32)
    rows = start + jnp.arange(c, dtype=jnp.int32)[:, None]      # [C, 1]
    first = (jnp.maximum(start - window + 1, 0) // key_block if window
             else jnp.int32(0))

    def block(i, carry):
        m, l, acc = carry
        # a page index past the table clamps to its last column: those
        # positions lie above every row's own and are masked below
        page = jnp.take(table, jnp.minimum(
            i * pages + jnp.arange(pages, dtype=jnp.int32),
            table.shape[0] - 1))
        kh = jnp.take(kc, page, axis=0).reshape(key_block, n_kv, dh)
        vh = jnp.take(vc, page, axis=0).reshape(key_block, n_kv, dh)
        if dv != dh:
            vh = vh[..., :dv]
        j = i * key_block + jnp.arange(key_block, dtype=jnp.int32)[None, :]
        sc = jnp.einsum('ckgd,tkd->ckgt', qh, kh.astype(mult),
                        precision=high,
                        preferred_element_type=jnp.float32) * scale
        seen = _in_window(j, rows, window)                      # [C, kb]
        sc = jnp.where(seen[:, None, None, :], sc, -jnp.inf)
        # a position no row of the chunk attends (a page the window has
        # passed, the tail past the chunk) may hold anything: its V must
        # not reach the sum, even at weight zero
        vh = jnp.where(jnp.any(seen, axis=0)[:, None, None],
                       vh.astype(mult), 0.0)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        base = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(m - base)
        p = jnp.exp(sc - base)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum('ckgt,tkd->ckgd', p.astype(mult), vh,
                                       precision=high,
                                       preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((c, n_kv, g, 1), -jnp.inf, jnp.float32),
            jnp.zeros((c, n_kv, g, 1), jnp.float32),
            jnp.zeros((c, n_kv, g, dv), jnp.float32))
    _, l, acc = jax.lax.fori_loop(first, (start + c - 1) // key_block + 1,
                                  block, init)
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(1, c, n_head * dv).astype(q.dtype)


def _chunk_write_rows(cache, kv, start, table):
    """kv_block_chunk_write with one index pair a ROW: cache [NB, BS, D],
    kv [R, C, D] of the pool's dtype, start [R] int32, table [R, MAXB]."""
    r, c, d = kv.shape
    pos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    bidx, boff = _block_scatter_idx(jnp.repeat(table, c, axis=0),
                                    pos.reshape(-1), cache.shape[1])
    return cache.at[bidx, boff].set(kv.reshape(r * c, d))


def _chunk_write_pages(cache, kv, start, table):
    """kv_block_chunk_write with one index a PAGE, for chunks of whole
    pages that start on a page's first row: row r's C positions are the
    C / BS whole pages that hold positions start[r], start[r] + BS, ... —
    each the block _block_scatter_idx gives that position."""
    r, c, d = kv.shape
    bs = cache.shape[1]
    pos = start[:, None] + jnp.arange(0, c, bs, dtype=jnp.int32)[None, :]
    bidx, _ = _block_scatter_idx(jnp.repeat(table, c // bs, axis=0),
                                 pos.reshape(-1), bs)
    return cache.at[bidx].set(kv.reshape(r * (c // bs), bs, d))


@register('kv_block_chunk_write', no_grad=True, lod='none')
def _kv_block_chunk_write(ctx, ins):
    """Chunked-prefill write: KV [R, C, D] — row r the K or V rows of
    chunk positions start[r]..start[r]+C-1 of ONE slot — into the block
    pool through that slot's table row (Cache [NB, BS, D], Start [R, 1]
    int32, BlockTable [R, MAXB] int32). Positions beyond a chunk's true
    length carry pad garbage into the slot's own tail block (or the trash
    block past the allocated span) — never attended before a decode step
    overwrites them, the prefill contract in block form; a pad ROW (the
    trash table) writes the trash block only. Out aliases Cache.

    Two bodies, chosen from the shapes the lowering sees and never from a
    knob, and told to the Tracer (lowered_bodies: 'pages' | 'rows'). A
    chunk of whole pages (C % BS == 0: every chunk program a
    configuration exports) whose rows all start on a page's first row —
    every slice the scheduler sends: prefix hits cover whole blocks and
    only a prompt's last slice is short — IS C / BS pages of its table
    and is written with one index a page (_chunk_write_pages: on a TPU a
    scatter costs by the index, 150 ns each, not by the byte); the same
    program sent a `start` inside a page takes the scatter of one index
    pair a row behind a branch on start % BS, and a C that is not whole
    pages holds that scatter alone (_chunk_write_rows). Outside the trash
    block the pool is the same to the bit either way, and either way it
    is updated in place."""
    cache = ins['Cache'][0]
    table = ins['BlockTable'][0]
    kv = ins['KV'][0].astype(cache.dtype)
    start = ins['Start'][0].reshape(kv.shape[0]).astype(jnp.int32)
    bs = cache.shape[1]
    pages = kv.shape[1] % bs == 0
    tracer = getattr(ctx, 'tracer', None)
    if tracer is not None:
        tracer.lowered_bodies.append(
            ('kv_block_chunk_write', 'pages' if pages else 'rows'))
    if not pages:
        return {'Out': [_chunk_write_rows(cache, kv, start, table)]}
    return {'Out': [jax.lax.cond(
        jnp.all(start % bs == 0), _chunk_write_pages, _chunk_write_rows,
        cache, kv, start, table)]}


@register('kv_block_chunk_attention', no_grad=True, lod='none')
def _kv_block_chunk_attention(ctx, ins):
    """Chunked-prefill attention: Q [R, C, D] — row r the chunk rows of
    one slot — attend that slot's logical view (KCache/VCache
    [NB, BS, D] through BlockTable row r of [R, MAXB]) rows
    j <= Start[r] + i — causal in the chunk and across everything
    already written (earlier chunks, SHARED prefix blocks, which is what
    lets a prefix hit skip recompute). Attrs n_kv_head and window as the
    step op's (_head_attrs).

    Two bodies, chosen from what the lowering sees and never from a
    knob, and told to the Tracer (lowered_bodies: 'gathered' |
    'blocked'): the gathered view under one softmax
    (_chunk_attention_body) where query and K/V heads are as many,
    nothing is windowed and the view's [R, C, n_head, T'] float32 scores
    are within _CHUNK_SCORES_BYTES (_gathered_view_fits: the size up to
    which the whole view is the faster body, whatever the slot has
    written); else the pages the slot HAS written, a block of positions
    at a time under an online softmax (_chunk_attention_blocked):
    another summation order; a latent pool (attr v_width) takes the
    second always, both products on operands of the pool's dtype. Only
    the gathered view has rows: with R = 1 it is
    the one-slot expression, unchanged, with more it is that function
    per row (one vmap); the blocked body's trip count depends on
    `start`, so it keeps R = 1 and refuses more by name."""
    q = ins['Q'][0]
    kc = ins['KCache'][0]
    vc = ins['VCache'][0]
    start = ins['Start'][0]
    tables = ins['BlockTable'][0].astype(jnp.int32)
    r, d = q.shape[0], kc.shape[2]
    n_head, n_kv, dh, _, window = _head_attrs(ctx, d)
    gathered = (_v_width(ctx, dh) == dh and _gathered_view_fits(
        r, q.shape[1], n_head, n_kv, window, tables.shape[1] * kc.shape[1]))
    tracer = getattr(ctx, 'tracer', None)
    if tracer is not None:
        tracer.lowered_bodies.append(
            ('kv_block_chunk_attention',
             'gathered' if gathered else 'blocked'))
    if r == 1:
        table = tables[0]
        if not gathered:
            return {'Out': [_chunk_attention_blocked(ctx, q, kc, vc,
                                                     start, table)]}
        kview = _block_view(kc, table)
        vview = _block_view(vc, table)
        return {'Out': [_chunk_attention_body(ctx, q, kview, vview, start,
                                              d)]}
    if not gathered:
        raise NotImplementedError(
            'kv_block_chunk_attention has rows (%d) over the gathered '
            'view only: _chunk_attention_blocked (grouped K/V heads, a '
            'window, scores past _CHUNK_SCORES_BYTES) keeps R = 1'
            % r)

    def row(q_r, start_r, table_r):
        return _chunk_attention_body(
            ctx, q_r[None], _block_view(kc, table_r),
            _block_view(vc, table_r), start_r, d)[0]
    return {'Out': [jax.vmap(row)(q, start.reshape(r), tables)]}


@register('kv_block_write_quant', no_grad=True, lod='none')
def _kv_block_write_quant(ctx, ins):
    """kv_block_write over the int8 block pool (composes ISSUE 11's
    quantized cache with block paging): Cache int8 [NB, BS, D], Scale
    f32 [NB, BS], KV f32 [S, D]. Rows quantize at their own abs-max
    page scale at write time; Out/OutScale alias Cache/Scale."""
    cache = ins['Cache'][0]
    cscale = ins['Scale'][0]
    kv = ins['KV'][0]
    pos = ins['Pos'][0].reshape(-1)
    table = ins['BlockTable'][0]
    q, s = _quantize_kv_rows(kv.astype(jnp.float32))
    bidx, boff = _block_scatter_idx(table, pos, cache.shape[1])
    return {'Out': [cache.at[bidx, boff].set(q)],
            'OutScale': [cscale.at[bidx, boff].set(s)]}


@register('kv_block_attention_quant', no_grad=True, lod='none')
def _kv_block_attention_quant(ctx, ins):
    """kv_block_attention over the int8 block pool: per-slot views
    dequantize (int8 page x its f32 scale) inside the body, then the
    exact fp masked-attention expression runs."""
    q = ins['Q'][0]
    kc = ins['KCache'][0]
    ks = ins['KScale'][0]
    vc = ins['VCache'][0]
    vs = ins['VScale'][0]
    pos = ins['Pos'][0].reshape(-1).astype(jnp.int32)
    table = ins['BlockTable'][0].astype(jnp.int32)

    def view(cache, scale, r):
        return (_block_view(cache, r).astype(jnp.float32)
                * _block_view(scale, r)[:, None])

    kview = jax.vmap(lambda r: view(kc, ks, r))(table)
    vview = jax.vmap(lambda r: view(vc, vs, r))(table)
    return {'Out': [_paged_attention_body(ctx, q, kview, vview, pos)]}


@register('kv_block_chunk_write_quant', no_grad=True, lod='none')
def _kv_block_chunk_write_quant(ctx, ins):
    """kv_block_chunk_write over the int8 block pool: chunk rows
    quantize per position (per block page) and scatter through the
    slot's table."""
    cache = ins['Cache'][0]
    cscale = ins['Scale'][0]
    kv = ins['KV'][0]
    _one_row_only('kv_block_chunk_write_quant', kv)
    start = ins['Start'][0].reshape(()).astype(jnp.int32)
    table = ins['BlockTable'][0]
    c = kv.shape[1]
    q, s = _quantize_kv_rows(kv[0].astype(jnp.float32))  # [C, D], [C]
    pos = start + jnp.arange(c, dtype=jnp.int32)
    bidx, boff = _block_scatter_idx(
        jnp.broadcast_to(table[0], (c, table.shape[1])), pos,
        cache.shape[1])
    return {'Out': [cache.at[bidx, boff].set(q)],
            'OutScale': [cscale.at[bidx, boff].set(s)]}


@register('kv_block_chunk_attention_quant', no_grad=True, lod='none')
def _kv_block_chunk_attention_quant(ctx, ins):
    """kv_block_chunk_attention over the int8 block pool. The CURRENT
    chunk's rows attend at FULL precision: K/V carry the fresh f32
    projections ([1, C, D], the same arrays the write op quantized) and
    splice over the view's span [start, start + C) — attend fresh f32,
    store int8, so a single-chunk prompt's prefill sees no quantization
    at all. Earlier
    chunks and shared prefix blocks exist only as int8 pages and
    dequantize — the unavoidable (and vLLM-standard) chunked-prefill
    quantization boundary."""
    q = ins['Q'][0]
    kc = ins['KCache'][0]
    ks = ins['KScale'][0]
    vc = ins['VCache'][0]
    vs = ins['VScale'][0]
    k_f = ins['K'][0]
    v_f = ins['V'][0]
    _one_row_only('kv_block_chunk_attention_quant', q)
    start = ins['Start'][0].reshape(()).astype(jnp.int32)
    table = ins['BlockTable'][0].astype(jnp.int32)[0]

    def spliced(cache, scale, fresh):
        view = (_block_view(cache, table).astype(jnp.float32)
                * _block_view(scale, table)[:, None])
        t, c = view.shape[0], fresh.shape[1]
        j = jnp.arange(t, dtype=jnp.int32)
        # gather (clipped: out-of-span rows are masked off below, and
        # clipping keeps every index in-bounds even for the final padded
        # chunk near the cache end)
        rel = jnp.clip(j - start, 0, c - 1)
        in_chunk = (j >= start) & (j < start + c)
        return jnp.where(in_chunk[:, None],
                         fresh[0][rel].astype(jnp.float32), view)

    kview = spliced(kc, ks, k_f)
    vview = spliced(vc, vs, v_f)
    return {'Out': [_chunk_attention_body(ctx, q, kview, vview, start,
                                          kc.shape[2])]}


# ---------------------------------------------------------------------------
# Speculative-decode VERIFY ops (ISSUE 17): one dispatch scores R = K+1
# token rows per slot over the paged cache — row 0 is the slot's last
# emitted token at its current write position p, rows 1..K are drafted
# tokens at p+1..p+K. KV is written speculatively for every fed row
# BEFORE attention runs (the step program's write-then-attend order),
# and row i attends j <= pos[s, i], so row i's logits see exactly the
# prefix a plain decode step would see after accepting rows < i — the
# bit-identity hinge of draft-and-verify. Rejection is a HOST decision:
# the scheduler rolls each slot's `pos` back to the accepted length, so
# rejected rows' cache garbage sits strictly above the attended
# frontier and is overwritten by the next real write before any mask
# ever admits it. Per-row positions encode the variable part inside the
# fixed [S, R] shape: pad rows carry pos = MAXB * BS (forced to the
# trash block by _block_scatter_idx's span guard — pos = T would hit a
# SHARED full prefix block at offset T % BS when T is not
# block-aligned). An unfed row writes nothing an attention mask can
# reach and its logits row is garbage the host never reads.
# ---------------------------------------------------------------------------

def _verify_attention_body(ctx, q, kc, vc, pos):
    """Multi-row masked attention for the verify program: Q [S, R, D]
    attends its slot's cache view with a PER-ROW frontier — row i sees
    j <= pos[s, i]. Row-wise it is exactly _paged_attention_body's
    expression (same einsum contraction order, same -inf mask, same
    softmax), which is what makes a verify row's output bit-comparable
    to the plain step's output at the same prefix."""
    s, t, d = kc.shape
    r = q.shape[1]
    n_head, n_kv, dh, scale, window = _head_attrs(ctx, d)
    if n_kv != n_head or window:
        raise NotImplementedError(
            'the speculative verify attention has neither grouped K/V '
            'heads (n_kv_head=%d of n_head=%d) nor a window (%d)'
            % (n_kv, n_head, window))
    qh = q.reshape(s, r, n_head, dh)
    kh = kc.reshape(s, t, n_head, dh)
    vh = vc.reshape(s, t, n_head, dh)
    scores = jnp.einsum('srhd,sthd->srht', qh, kh) * scale
    valid = (jnp.arange(t, dtype=jnp.int32)[None, None, :]
             <= pos[:, :, None])
    scores = jnp.where(valid[:, :, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    ctxv = jnp.einsum('srht,sthd->srhd', w, vh)
    return ctxv.reshape(s, r, d).astype(q.dtype)


@register('kv_block_verify_write', no_grad=True, lod='none')
def _kv_block_verify_write(ctx, ins):
    """Write R = K+1 speculative K or V rows per slot into the block
    pool: Cache [NB, BS, D], KV [S, R, D], Pos [S, R] int32, BlockTable
    [S, MAXB] int32. Each
    slot's table broadcasts over its R rows; pad rows carry
    pos = MAXB * BS, which _block_scatter_idx forces to the trash block
    (colliding trash scatters are write-racy but never read — the
    existing idle-row contract). The scheduler CoW/extends every block
    in the speculative span first, so real indices land only in
    uniquely-owned blocks. Out aliases Cache."""
    cache = ins['Cache'][0]
    kv = ins['KV'][0]
    pos = ins['Pos'][0].astype(jnp.int32)
    table = ins['BlockTable'][0]
    s, r = pos.shape
    wide = jnp.broadcast_to(table[:, None, :],
                            (s, r, table.shape[1])).reshape(s * r, -1)
    bidx, boff = _block_scatter_idx(wide, pos.reshape(-1),
                                    cache.shape[1])
    return {'Out': [cache.at[bidx, boff].set(
        kv.reshape(s * r, -1).astype(cache.dtype))]}


@register('kv_block_verify_attention', no_grad=True, lod='none')
def _kv_block_verify_attention(ctx, ins):
    """Verify attention over the block pool: Q [S, R, D], Pos [S, R]
    int32. Per-slot logical views gather through the table, then the
    shared verify body masks row i at j <= pos[s, i] — the speculative
    rows written this dispatch included, so row i's window is exactly
    the plain step's window after accepting the i drafted tokens before
    it. Masked rows get exactly-zero weight, so
    foreign blocks, trash garbage, and rejected speculative rows above
    a frontier can never perturb an accepted row's output."""
    q = ins['Q'][0]
    kc = ins['KCache'][0]
    vc = ins['VCache'][0]
    pos = ins['Pos'][0].astype(jnp.int32)
    table = ins['BlockTable'][0].astype(jnp.int32)
    kview = jax.vmap(lambda rw: _block_view(kc, rw))(table)
    vview = jax.vmap(lambda rw: _block_view(vc, rw))(table)
    return {'Out': [_verify_attention_body(ctx, q, kview, vview, pos)]}


@register('kv_block_verify_write_quant', no_grad=True, lod='none')
def _kv_block_verify_write_quant(ctx, ins):
    """kv_block_verify_write over the int8 block pool: speculative rows
    quantize at their own abs-max page scale and scatter with their
    scales through the broadcast tables (pad rows to the trash
    block)."""
    cache = ins['Cache'][0]
    cscale = ins['Scale'][0]
    kv = ins['KV'][0]
    pos = ins['Pos'][0].astype(jnp.int32)
    table = ins['BlockTable'][0]
    s, r = pos.shape
    q, sc = _quantize_kv_rows(kv.astype(jnp.float32))
    wide = jnp.broadcast_to(table[:, None, :],
                            (s, r, table.shape[1])).reshape(s * r, -1)
    bidx, boff = _block_scatter_idx(wide, pos.reshape(-1),
                                    cache.shape[1])
    return {'Out': [cache.at[bidx, boff].set(q.reshape(s * r, -1))],
            'OutScale': [cscale.at[bidx, boff].set(sc.reshape(-1))]}


@register('kv_block_verify_attention_quant', no_grad=True, lod='none')
def _kv_block_verify_attention_quant(ctx, ins):
    """kv_block_verify_attention over the int8 block pool: per-slot
    views dequantize (int8 page x its f32 scale) inside the body, then
    the exact fp verify expression runs."""
    q = ins['Q'][0]
    kc = ins['KCache'][0]
    ks = ins['KScale'][0]
    vc = ins['VCache'][0]
    vs = ins['VScale'][0]
    pos = ins['Pos'][0].astype(jnp.int32)
    table = ins['BlockTable'][0].astype(jnp.int32)

    def view(cache, scale, rw):
        return (_block_view(cache, rw).astype(jnp.float32)
                * _block_view(scale, rw)[:, None])

    kview = jax.vmap(lambda rw: view(kc, ks, rw))(table)
    vview = jax.vmap(lambda rw: view(vc, vs, rw))(table)
    return {'Out': [_verify_attention_body(ctx, q, kview, vview, pos)]}


# ---------------------------------------------------------------------------
# beam search (fixed-width; see module docstring)
# ---------------------------------------------------------------------------

@register('beam_search', no_grad=True, lod='aware')
def _beam_search(ctx, ins):
    """One decode step. Rows are [B*K]: K beams per source. Candidate ids /
    accumulated scores are [B*K, C] (C candidates per beam, usually a
    pre-topk). Selects the top K of the K*C candidates per source.
    Finished beams (pre_id == end_id) contribute a single frozen candidate.
    Outputs parent_idx (absolute row of each selected beam's parent) for
    beam_search_decode's backtrace — the information the reference encodes
    in the output LoD."""
    pre_ids = unwrap(ins['pre_ids'][0]).reshape(-1)         # [B*K]
    pre_scores = unwrap(ins['pre_scores'][0]).reshape(-1)   # [B*K]
    ids = unwrap(ins['ids'][0]) if ins.get('ids') and ins['ids'][0] is not None else None
    scores = unwrap(ins['scores'][0])                       # [B*K, C]
    K = int(ctx.attr('beam_size'))
    end_id = int(ctx.attr('end_id'))
    if ids is None:
        ids = jnp.broadcast_to(jnp.arange(scores.shape[1], dtype=INT_T()),
                               scores.shape)
    ids = ids.astype(INT_T())
    BK, C = scores.shape
    B = BK // K
    neg_inf = jnp.asarray(-1e9, scores.dtype)

    finished = pre_ids == end_id                            # [B*K]
    # frozen candidate 0 for finished beams; others -inf
    cand_scores = jnp.where(finished[:, None],
                            jnp.concatenate(
                                [pre_scores[:, None],
                                 jnp.full((BK, C - 1), neg_inf, scores.dtype)],
                                axis=1) if C > 1 else pre_scores[:, None],
                            scores)
    cand_ids = jnp.where(finished[:, None],
                         jnp.full((BK, C), end_id, INT_T()), ids)

    g_scores = cand_scores.reshape(B, K * C)
    g_ids = cand_ids.reshape(B, K * C)
    top_s, top_i = jax.lax.top_k(g_scores, K)               # [B, K]
    sel_ids = jnp.take_along_axis(g_ids, top_i, axis=1)     # [B, K]
    parent = top_i // C + (jnp.arange(B, dtype=jnp.int32)[:, None] * K)
    return {'selected_ids': [sel_ids.reshape(-1, 1)],
            'selected_scores': [top_s.reshape(-1, 1)],
            'parent_idx': [parent.reshape(-1).astype(jnp.int32)]}


@register('beam_search_decode', no_grad=True, lod='aware')
def _beam_search_decode(ctx, ins):
    """Backtrace TensorArrays of per-step (ids, scores, parents) into full
    hypotheses [B*K rows x T tokens]; rows padded with end_id after each
    hypothesis ends (static shapes; the reference emits a dynamic LoD)."""
    from ..core.tensor_array import TensorArrayVal
    ids_arr = ins['Ids'][0]
    scores_arr = ins['Scores'][0]
    parents_arr = ins['Parents'][0] if ins.get('Parents') and \
        ins['Parents'][0] is not None else None
    end_id = int(ctx.attr('end_id'))
    if not isinstance(ids_arr, TensorArrayVal) or ids_arr.data is None:
        raise TypeError("beam_search_decode needs written TensorArrays")
    ids = ids_arr.data.reshape(ids_arr.capacity, -1)        # [T, BK]
    scores = scores_arr.data.reshape(scores_arr.capacity, -1)
    T, BK = ids.shape
    rows = jnp.arange(BK, dtype=jnp.int32)
    if parents_arr is not None and parents_arr.data is not None:
        parents = parents_arr.data.reshape(T, BK).astype(jnp.int32)
    else:
        parents = jnp.broadcast_to(rows, (T, BK))

    # walk backwards from the WRITTEN length, not capacity: unwritten slots
    # (t >= length) are identity links emitting end_id so they neither
    # corrupt the parent chain nor the tokens
    length = ids_arr.length
    valid = jnp.arange(T, dtype=jnp.int32) < length         # [T]

    def back(beam, inp):
        ids_t, par_t, v_t = inp
        tok = jnp.where(v_t, ids_t[beam], end_id)
        prev = jnp.where(v_t, par_t[beam], beam)
        return prev, tok

    _, toks_rev = jax.lax.scan(
        back, rows,
        (ids[::-1].astype(INT_T()), parents[::-1], valid[::-1]))
    sent = toks_rev[::-1]                                   # [T, BK]
    sent = jnp.moveaxis(sent, 1, 0)                         # [BK, T]
    # freeze everything after the first end_id to end_id
    seen_end = jnp.cumsum((sent == end_id).astype(jnp.int32), axis=1) > 0
    shifted = jnp.concatenate(
        [jnp.zeros((BK, 1), bool), seen_end[:, :-1]], axis=1)
    sent = jnp.where(shifted, end_id, sent)
    final_scores = jax.lax.dynamic_index_in_dim(
        scores, jnp.maximum(length - 1, 0), 0, keepdims=False).reshape(-1, 1)
    lod = [lengths_to_offsets([T] * BK)]
    return {'SentenceIds': [LoDArray(sent.reshape(-1, 1), lod)],
            'SentenceScores': [LoDArray(
                jnp.broadcast_to(final_scores, (BK, T)).reshape(-1, 1), lod)]}
