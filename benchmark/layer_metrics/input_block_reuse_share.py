"""Share of the batches' large leaves that ``ArraySource.fetch`` gathered
into a block of memory it had written before, and not into a fresh
allocation: ``hvtpu_data_fetch_blocks_total{block="reused"}`` over both
labels' totals, for the whole process (a dozen fetches of set-up and the
traced window's are in it; the untraced window's are nine in ten).  A
fresh block of a batch's size is faulted in page by page, which cost
twelve times the gather itself on the chip's host (PERF.md section 6,
PR 26), so at a low share the fetch is slow again: a consumer that keeps
its host batches, or copies to the chips that hold more blocks at once
than the pool keeps.  0 where no leaf reached the pooled size: nothing was
reused.  None on a commit whose program has no such counter."""

LAYER, UNIT, MOVES = "input", "%", "samples_per_s_per_chip"

COUNTER = "hvtpu_data_fetch_blocks_total"


def read(obs):
    try:
        from horovod_tpu.obs import metrics
    except ImportError:
        return None
    family = metrics.snapshot().get(COUNTER)
    if family is None:
        return None
    reused = family["values"].get('{block="reused"}', 0.0)
    fresh = family["values"].get('{block="fresh"}', 0.0)
    return 100.0 * reused / (reused + fresh) if reused + fresh else 0.0
