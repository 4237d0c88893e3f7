"""The LOGITS a DecodingPredictor's programs compute for given
prompts — chunked prefill, then greedy decode steps through the block
cache — taken through the predictor's own dispatch functions (fetch 1 of
the chunk and the step programs, asked for with `logits=True` and read
with `_to_host`; the
scheduler itself copies only fetch 0, the ids), for comparison with a
plain reference's
full forward pass. Transcripts alone cannot carry that comparison: with
random weights the largest logit changes on rounding.

Like warmup(), this dispatches on the scheduler's donated state from the
caller's thread: use it on a predictor that has served nothing yet (or is
idle); the cache is re-zeroed afterwards."""
import numpy as np

from ..inference.kv_blocks import WindowTable


def served_logits(pred, prompts, n_new):
    """Each of `prompts` (at most max_slots) is prefilled into a slot of
    its own in slices of the artifact's chunk sizes, then all decode
    together for n_new - 1 steps, each feeding its own argmax. Returns
    (tokens, logits): per prompt n_new greedy tokens and the [n_new, vocab]
    float32 rows that chose them — row 0 from the prompt's last chunk
    (scoring position len(prompt)), row j from decode step j."""
    if len(prompts) > pred.max_slots:
        raise ValueError('more prompts than slots')
    S, maxb, chunks = pred.max_slots, pred._maxb, pred._chunks
    tables = np.full((S, maxb), pred._trash, np.int32)
    for i in range(len(prompts)):       # full capacity: a private span
        tables[i] = 1 + i * maxb + np.arange(maxb)
    # window layers: the tables the scheduler itself would keep, from
    # the predictor's own block manager — blocks the window has passed
    # are given back (and handed to the next prompt) as it goes
    window = pred._window
    wrows = [WindowTable() for _ in prompts] if window else None

    def wtable(i, first, end):
        pred._blocks.window_advance(wrows[i], first - window + 1, end)
        return wrows[i].fill(np.full(maxb, pred._trash, np.int32))[None]

    rows = []
    for i, prompt in enumerate(prompts):
        prompt = np.asarray(prompt, np.int64)
        start = 0
        while start < len(prompt):
            left = len(prompt) - start
            size = next((c for c in chunks if c >= left), chunks[-1])
            take = min(size, left)
            ids = np.zeros((1, size), np.int64)
            ids[0, :take] = prompt[start:start + take]
            read = pred._dispatch_chunk(
                size, ids, start, take, tables[i:i + 1], logits=True,
                window_row=wtable(i, start, start + take) if window
                else None)
            lg = pred._to_host(read)[1][0]
            start += take
        rows.append([np.array(lg, np.float32)])
    for j in range(1, n_new):
        tok = np.zeros((S, 1), np.int64)
        pos = np.zeros((S, 1), np.int32)
        for i, prompt in enumerate(prompts):
            tok[i, 0] = int(np.argmax(rows[i][-1]))
            pos[i, 0] = len(prompt) + j - 1
        wtables = None
        if window:
            wtables = np.concatenate(
                [wtable(i, int(pos[i, 0]), int(pos[i, 0]) + 1)
                 for i in range(len(prompts))]
                + [np.full((S - len(prompts), maxb), pred._trash, np.int32)])
        _, lg = pred._to_host(
            pred._dispatch_step(tok, pos, tables=tables, logits=True,
                                wtables=wtables))
        for i in range(len(prompts)):
            rows[i].append(np.array(lg[i], np.float32))
    pred._reset_state()
    pred.stats.reset()
    logits = [np.stack(r) for r in rows]
    return [[int(t) for t in lg.argmax(-1)] for lg in logits], logits
