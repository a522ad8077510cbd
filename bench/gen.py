"""Traffic: the synthetic Table-4 access streams, cut into segments.

The access model is a copy of the program's ``repro.sim.trace_gen``
(hot/cold page skew, a contiguous hot region, sequential runs, page
re-references, 4K and 2M pages), so that a later change to the program
cannot move the yardstick.  What differs is how it is cut:

- a workload's parameters come from the traffic file, not a registry;
- each workload is one stream per seed, made of blocks of
  ``block_accesses``; block ``b`` is drawn from its own generator seeded
  by ``(seed, b, crc32(name))``, so the stream extends without bound and
  any segment can be regenerated on its own;
- a traffic file with ``"cores": C`` runs multiprogrammed ``mixes`` of C
  workloads: core ``c`` of mix ``m`` is a stream of its own, seeded by
  ``(seed, b, crc32(name), m, c)``, so two cores on one workload never
  alias;
- segment ``j`` of a workload is accesses ``[j*seg, (j+1)*seg)`` of its
  stream, so consecutive segments tile the stream;
- a block is drawn ``4 * reref_window`` accesses longer and that head
  is dropped: there a re-reference (chains of up to four) would reach
  back before the start and land on the first access, so the head of
  the program's traces, all that a short segment would read, revisits
  one page.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np

GB = 1 << 30
PAGE4 = 4096
LINES_PER_PAGE4 = 64
LINE_REUSE_FRAC = 0.18
MAX_PAGES4 = 1 << 23


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    name: str
    footprint_gb: float
    thp_frac: float
    zipf_a: float | None
    seq_frac: float
    seq_run: int
    ipa: float = 3.0
    reref_frac: float = 0.0
    reref_window: int = 2000
    hot_frac: float = 0.55
    hot_pages: int = 32_000


def _zipf_pages(rng, n: int, n_pages: int, a: float) -> np.ndarray:
    r = rng.zipf(a + 1e-9 if a > 1.0 else 1.0001, size=n)
    r = np.minimum(r - 1, n_pages - 1)
    salt = np.uint64(0x9E3779B97F4A7C15)
    pr = (r.astype(np.uint64) * salt) % np.uint64(n_pages)
    return pr.astype(np.int64)


def block(spec: WorkloadSpec, n: int, seed: int, index: int,
          lane: tuple[int, ...] = ()) -> dict:
    """Block ``index`` of ``spec``'s stream: {vpn, is2m, line}, [n].
    ``lane`` is ``(mix, core)`` for a core of a multiprogrammed mix."""
    lead = 4 * spec.reref_window if spec.reref_frac > 0 else 0
    out = _draw(spec, n + lead, seed, index, lane)
    return {k: v[lead:] for k, v in out.items()}


def _draw(spec: WorkloadSpec, n: int, seed: int, index: int,
          lane: tuple[int, ...]) -> dict:
    rng = np.random.default_rng(
        [int(seed), int(index), zlib.crc32(spec.name.encode()),
         *map(int, lane)])
    n_pages = min(int(spec.footprint_gb * GB / PAGE4), MAX_PAGES4)
    n4 = int(n_pages * (1.0 - spec.thp_frac))
    n4 = max(512, n4 - (n4 % 512))

    if spec.zipf_a is None:
        pages = rng.integers(0, n_pages, size=n, dtype=np.int64)
    else:
        pages = _zipf_pages(rng, n, n_pages, spec.zipf_a)

    if spec.hot_frac > 0:
        hot = min(spec.hot_pages, n_pages)
        hot_ids = rng.integers(0, hot, size=n)
        in_hot = rng.random(n) < spec.hot_frac
        pages = np.where(in_hot, hot_ids, pages)

    if spec.seq_frac > 0:
        n_seq = int(n * spec.seq_frac)
        run_pages = max(spec.seq_run // LINES_PER_PAGE4, 1)
        n_runs = max(1, n_seq // run_pages)
        starts = rng.integers(0, max(n_pages - run_pages, 1), size=n_runs)
        seq = (starts[:, None] + np.arange(run_pages)[None, :]).reshape(-1)
        seq = seq[:n_seq]
        pos = rng.choice(n, size=len(seq), replace=False)
        pages[pos] = seq

    line_in_page = rng.integers(0, LINES_PER_PAGE4, size=n, dtype=np.int64)

    if spec.reref_frac > 0:
        u = rng.random(n)
        d = rng.integers(1, spec.reref_window, size=n)
        src = np.maximum(np.arange(n) - d, 0)
        take = u < spec.reref_frac
        for _ in range(4):
            pages = np.where(take, pages[src], pages)
        same_line = take & (rng.random(n) < LINE_REUSE_FRAC)
        for _ in range(4):
            line_in_page = np.where(same_line, line_in_page[src],
                                    line_in_page)

    pages = pages % n_pages
    return {"vpn": pages.astype(np.int32),
            "is2m": pages >= n4,
            "line": (pages * LINES_PER_PAGE4 + line_in_page).astype(np.int32)}


class Stream:
    """One workload's access stream for one seed, read segment by segment."""

    def __init__(self, spec: WorkloadSpec, seed: int, seg: int,
                 block_accesses: int, lane: tuple[int, ...] = ()):
        if seg <= 0 or block_accesses % seg:
            raise ValueError(f"segment length {seg} must divide the block "
                             f"length {block_accesses}")
        self.spec, self.seed, self.seg = spec, seed, seg
        self.block_accesses = block_accesses
        self.lane = tuple(lane)
        self._blocks: dict[int, dict] = {}

    def segment(self, j: int) -> dict:
        """Accesses ``[j*seg, (j+1)*seg)`` of the stream, with ``ipa``."""
        b, off = divmod(j * self.seg, self.block_accesses)
        if b not in self._blocks:
            self._blocks = {b: block(self.spec, self.block_accesses,
                                     self.seed, b, self.lane)}
        blk = self._blocks[b]
        out = {k: v[off:off + self.seg] for k, v in blk.items()}
        out["ipa"] = np.full((self.seg,), self.spec.ipa, np.float32)
        return out


def workload_specs(traffic: dict) -> list[WorkloadSpec]:
    return [WorkloadSpec(**w) for w in traffic["workloads"]]


def sources(traffic: dict, seed: int) -> list[list[Stream]]:
    """The streams of each column source: one per workload, or, where
    the file declares ``cores``, C per mix (core ``c`` of mix ``m``
    runs ``mixes[m][c]`` on its own stream)."""
    seg, blk = int(traffic["seg_accesses"]), int(traffic["block_accesses"])
    specs = workload_specs(traffic)
    cores = int(traffic.get("cores", 1))
    if cores == 1:
        if "mixes" in traffic:
            raise ValueError("mixes need \"cores\" above 1")
        return [[Stream(s, seed, seg, blk)] for s in specs]
    by_name = {s.name: s for s in specs}
    mixes = traffic.get("mixes")
    if not mixes or any(len(mix) != cores for mix in mixes):
        raise ValueError(f"every mix must list {cores} workloads")
    return [[Stream(by_name[n], seed, seg, blk, (m, c))
             for c, n in enumerate(mix)] for m, mix in enumerate(mixes)]


def source_segment(source: list[Stream], j: int) -> dict:
    """Segment ``j`` of one column source: [T] leaves for a workload,
    [T, C] leaves and a ``core`` leaf (int32, value c) for a mix."""
    if len(source) == 1:
        return source[0].segment(j)
    out = stack([s.segment(j) for s in source])
    out["core"] = np.broadcast_to(
        np.arange(len(source), dtype=np.int32), out["vpn"].shape)
    return out


def dispatch_plan(n_workloads: int, width: int, k: int) -> list[tuple[int, int]]:
    """(workload or mix index, segment index) of each column of
    dispatch ``k``.

    Dispatch ``k`` takes workloads ``width*k .. width*k + width-1``
    (mod the workload count), as a ladder fill chunks its workload (or
    mix) list; each workload's segment index counts the earlier
    dispatches it was in, so every column reads the next unread segment
    of its stream.
    """
    cols = []
    for c in range(width):
        pos = width * k + c
        w = pos % n_workloads
        cols.append((w, pos // n_workloads))
    return cols


def stack(segments: list[dict]) -> dict[str, np.ndarray]:
    """Columns of one dispatch, stacked to [T, W] leaves ([T, W, C] for
    mixes)."""
    return {k: np.stack([s[k] for s in segments], axis=1)
            for k in segments[0]}
