"""Interval arithmetic shared by the runtime's accounts and the trace
analyzer (stdlib only: ``obs/report.py`` must stay importable without
JAX)."""

from __future__ import annotations


def union_seconds(
    intervals: list[tuple[float, float]],
    lo: float = float("-inf"),
    hi: float = float("inf"),
) -> float:
    """Seconds of ``[lo, hi]`` covered by the union of possibly-overlapping
    ``(start, end)`` intervals."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def idle_split(
    shards: list[tuple[int, float | None, float, float | None]],
    uploads: dict[int, tuple[float, float]],
    t_end: float,
    block_rows: tuple[float, ...] = (),
) -> list[tuple[float, float, float]]:
    """Why the device stood idle between a sweep's shards, from host stamps
    on one clock. ``shards``: per shard the consumer took, in order,
    ``(shard_idx, t_launch, t_last, t_ready)``: its first block's steps
    enqueued, its last block's, and the wait for the device at its end
    returned (``t_ready`` None for a shard that has none: it is accounted
    with the next one, the sweep's last up to ``t_end``; a shard that
    launched nothing has ``t_launch`` None and reads zeros). ``uploads``:
    ``shard_idx -> (t_enqueue, t_done)`` of the weight uploads seen to
    completion, ``t_enqueue`` where the upload's ``device_put`` call
    RETURNED: an upload of many leaves goes in leaf by leaf for
    milliseconds, and a block launched during the call queues behind the
    leaves already in, not behind the upload, so it counts as launched
    before it. ``block_rows``: the rows of each block a shard is dispatched
    over, in order (what a block's device time is taken to grow with).
    Per shard, in order, ``(drained_s, own_upload_wait_s,
    behind_upload_s)``:

    - ``drained_s``: ``t_launch`` less the previous shard's ``t_ready``:
      the device's last result was on the host and nothing enqueued behind
      it (0 where the previous shard has no ``t_ready``);
    - ``own_upload_wait_s``: launched before its own upload had arrived:
      ``min(t_done(own), t_ready) - t_launch``;
    - ``behind_upload_s``: launched behind a transfer it does not need. A
      launch queues behind every transfer enqueued before it, and a shard
      launches once a block, so the uploads that count are the OTHER
      shards' enqueued before this shard's LAST launch (``t_last``), still
      under way at its first and ARRIVED before ``t_ready`` (a shard that
      was done before an upload arrived did not wait for it, whatever the
      host's stamps say of the order): from ``max(t_launch, t_done(own),
      the earliest of those enqueues)`` to the latest of their arrivals
      ``T``. Where that enqueue falls between two of
      the shard's launches (taken as evenly spaced from ``t_launch`` to
      ``t_last``), the blocks dispatched before it still run: their device
      time, estimated from the later blocks' (``t_ready - T``) in
      proportion to ``block_rows``, is taken off the front.
    """
    ready, nxt = [t_end] * len(shards), t_end
    for i in range(len(shards) - 1, -1, -1):
        if shards[i][3] is not None:
            nxt = shards[i][3]
        ready[i] = nxt
    out = []
    prev_ready = None
    for (idx, t_launch, t_last, t_ready), bound in zip(shards, ready):
        if t_launch is None:
            out.append((0.0, 0.0, 0.0))
            prev_ready = t_ready
            continue
        drained = 0.0 if prev_ready is None else max(0.0, t_launch - prev_ready)
        own_done = uploads[idx][1] if idx in uploads else float("-inf")
        own = max(0.0, min(own_done, bound) - t_launch)
        ahead = [
            (enq, done) for k, (enq, done) in uploads.items()
            if k != idx and enq < t_last and t_launch < done < bound
        ]
        behind = 0.0
        if ahead:
            enq, arrival = min(e for e, _ in ahead), max(d for _, d in ahead)
            busy_until = t_launch + _early_blocks_s(
                enq, t_launch, t_last, bound - arrival, block_rows
            )
            start = max(t_launch, own_done, enq, busy_until)
            behind = max(0.0, arrival - start)
        out.append((drained, own, behind))
        prev_ready = t_ready
    return out


def _early_blocks_s(
    enq: float, t_launch: float, t_last: float, late_s: float,
    block_rows: tuple[float, ...],
) -> float:
    """Device seconds of the blocks a shard dispatched BEFORE a transfer
    enqueued at ``enq`` (they run; the later ones queue behind it): the
    later blocks took ``late_s`` on the device, and a block's time is taken
    to grow with its rows. Launches evenly spaced from ``t_launch`` (the
    first block's) to ``t_last``. 0 where nothing can be said."""
    n = len(block_rows)
    if n < 2 or enq <= t_launch or late_s <= 0.0 or t_last <= t_launch:
        return 0.0
    step = (t_last - t_launch) / (n - 1)
    early = min(n - 1, int((enq - t_launch) / step) + 1)  # blocks 0..early-1
    late_rows = sum(block_rows[early:])
    return late_s * sum(block_rows[:early]) / late_rows if late_rows else 0.0
