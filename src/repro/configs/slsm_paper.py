"""The paper's own tuned baseline (Section 3): mu=512, eps=0.001, R=50,
Rn=800, D=20, m=1.0 — used by benchmarks and examples.

`repro.bench.scenarios.bench_params` is the CPU-scaled sibling (same
ratios, sizes that run in seconds); the BENCH_*.json trajectory and the
figure benches both measure that configuration.

`paper_params()` as shipped (max_levels=3) preallocates 107.6 GiB of
device state — four (20, 323,584,000) int32 planes at its deepest tier
plus 10.8 GiB of Bloom words — so no single chip holds it.
`one_chip_params()` is the largest geometry with the Section 3 ratios
that one TPU v5e (16 GiB HBM) holds: the same widths with one tier
fewer, 5.38 GiB of state (6.46 GiB as laid out on the TPU, which pads
the 20-run axis to 24 rows). Its cuts are listed in `ONE_CHIP_REDUCED`;
`chip_smoke.py` runs it end to end.

These knobs are a *static* pick — one point in the paper's Table 1
space, chosen by hand. Since the tuner PR the engine can also pick for
itself: ``paper_params(tuning=TuningPolicy(mode="adaptive"))`` lets
`repro.engine.tuner` re-partition the memory budget (write buffer vs
per-level Bloom bits vs fence granularity) at merge boundaries as the
observed workload shifts — the README's Tuning guide and DESIGN.md §9
describe when to prefer which.
"""
from repro.core.params import SLSMParams, TuningPolicy  # noqa: F401  (re-
# exported so `paper_params(tuning=TuningPolicy(...))` needs one import)

PAPER_BASELINE = SLSMParams(R=50, Rn=800, eps=1e-3, D=20, m=1.0, mu=512,
                            max_levels=3)


def paper_params(**overrides) -> SLSMParams:
    """Section 3 baseline with keyword overrides (e.g. laptop scaling:
    ``paper_params(R=8, Rn=256, D=4, mu=64)``)."""
    base = dict(R=50, Rn=800, eps=1e-3, D=20, m=1.0, mu=512, max_levels=3)
    base.update(overrides)
    return SLSMParams(**base)


# the knobs a deployment runs beside the paper's geometry (the bench's own
# choices, repro.bench.scenarios.bench_params): paced merges and a
# bounded range-scan candidate budget (at this size an unbounded budget
# makes every scan's candidate row ~324M lanes wide)
ONE_CHIP_KNOBS = dict(max_levels=2, merge_budget=1, range_cand=512,
                      max_range=4096)

# each cut of scale from the Section 3 geometry, for reports
ONE_CHIP_REDUCED = [
    "max_levels 3 -> 2: three preallocated tiers need 107.6 GiB of "
    "device state, two need 5.38 GiB; with two tiers a deepest "
    "compaction overflows once level 1 holds more than level_cap(1) = "
    "16,179,200 live distinct keys, so that is what the tree sustains "
    "under continued writes",
]


def one_chip_params() -> SLSMParams:
    """The Section 3 geometry cut to what one TPU v5e holds (module
    docstring; cuts in `ONE_CHIP_REDUCED`). The deepest compaction, the
    largest program, peaks near 7 GiB of the chip's 16 GiB. It raises,
    and drops the engine's state, when level 1 holds more than
    16,179,200 live distinct keys: data meant for sustained writes stays
    below that (`chip_smoke.py` loads 20M distinct keys, past it, and
    stops writing before a second deepest compaction)."""
    return paper_params(**ONE_CHIP_KNOBS)
