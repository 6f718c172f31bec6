"""Continuous-batching serving demo: mixed-length requests through
``dsml_tpu.serving.ContinuousBatcher`` vs the static-batch baseline.

The reference has no inference path (SURVEY.md §5; its client only trains);
the framework's ``generate`` already does batched decode. This example shows
the scheduling layer on top: requests with different prompt/output lengths
are served slot-based — a finished request's slot is refilled from the
queue immediately, where a static batch idles every lane until the longest
request finishes. Prints per-strategy wall time and decode-lane utilization.

Run (CPU): python examples/serve_continuous.py --platform cpu --requests 12
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

sys.path.insert(0, ".")  # repo-root invocation

from dsml_tpu.utils.config import Config, field
from dsml_tpu.utils.logging import get_logger

log = get_logger("serve")


@dataclasses.dataclass
class ServeConfig(Config):
    platform: str = field("", help="jax platform override: cpu|tpu ('' = default)")
    cpu_devices: int = field(0, help="virtual CPU device count for --platform cpu")
    family: str = field("gpt2", help="model family: gpt2 | llama")
    model: str = field("tiny", help="model preset (tiny for the demo)")
    n_slots: int = field(4, help="decode slots (concurrent requests)")
    quantum: int = field(1, help="tokens decoded per scheduler tick (one jitted "
                         "scan; amortizes the per-tick host round trip)")
    adaptive: int = field(0, help="adaptive early-exit tick budget: one device "
                          "dispatch decodes until any slot finishes (or this "
                          "many steps); 0 = off")
    turbo: int = field(0, help="turbo factor: compile a second decode program "
                       "with quantum*turbo tokens/tick and escalate to it in "
                       "steady-state decode (0 = off)")
    prefill_chunk: int = field(0, help="chunked-prefill admission: prefill C "
                               "tokens per tick with decode quanta between a "
                               "long prompt's chunks (0 = whole-prompt)")
    requests: int = field(12, help="number of requests in the workload")
    max_new_max: int = field(24, help="largest per-request token budget")
    temperature: float = field(0.0, help="0 = greedy")
    seed: int = field(0, help="workload seed")


def main() -> None:
    cfg = ServeConfig.parse_args()
    if cfg.platform:
        from dsml_tpu.utils.platform import configure_platform

        configure_platform(cfg.platform, cfg.cpu_devices or None)

    from dsml_tpu.models import model_by_family
    from dsml_tpu.serving import ContinuousBatcher

    model, mcfg = model_by_family(cfg.family, cfg.model)
    params = model.init(cfg.seed)

    rng = np.random.default_rng(cfg.seed)
    lengths = rng.integers(4, min(64, mcfg.max_seq // 2), cfg.requests)
    budgets = rng.integers(2, cfg.max_new_max + 1, cfg.requests)
    prompts = [rng.integers(0, mcfg.vocab_size, (l,)).astype(np.int32) for l in lengths]
    total_tokens = int(budgets.sum())
    log.info(
        "workload: %d requests, prompts %d-%d tokens, budgets %d-%d, %d total new tokens",
        cfg.requests, lengths.min(), lengths.max(), budgets.min(), budgets.max(),
        total_tokens,
    )

    # ---- continuous batching ---------------------------------------------------
    srv = ContinuousBatcher(
        model, params, n_slots=cfg.n_slots, temperature=cfg.temperature,
        seed=cfg.seed, prompt_buckets=(16, 32, 64), decode_quantum=cfg.quantum,
        turbo_factor=cfg.turbo, prefill_chunk=cfg.prefill_chunk,
        adaptive_quantum=cfg.adaptive,
    )
    # warmup pass: compile every bucket's prefill + the decode program so
    # the timed pass measures steady-state serving, not compilation
    for p, n in zip(prompts, budgets):
        srv.submit(p, int(n))
    srv.run()
    rids = [srv.submit(p, int(n)) for p, n in zip(prompts, budgets)]
    # warmup's dispatches
    plain0, turbo0, adapt0 = (srv.n_plain_ticks, srv.n_turbo_ticks,
                              srv.n_adaptive_ticks)
    t0 = time.monotonic()
    steps = 0
    useful_ticks = 0  # decode-lane ticks that produced a wanted token
    while srv.n_queued or srv.n_active:
        useful_ticks += sum(len(v) for v in srv.step().values())
        steps += 1
    cont_s = time.monotonic() - t0
    srv.collect()
    n_plain = srv.n_plain_ticks - plain0
    n_turbo = srv.n_turbo_ticks - turbo0
    n_adapt = srv.n_adaptive_ticks - adapt0
    # decode-lane capacity actually dispatched this pass (turbo ticks carry
    # turbo x the base quantum). useful_ticks counts every emitted token
    # including each request's prefill-sampled FIRST token, which consumes
    # no decode lane — drop those so utilization stays <= 100%
    useful_ticks -= cfg.requests
    lane_capacity = (n_plain + n_turbo * max(cfg.turbo, 1)) * cfg.quantum * cfg.n_slots

    # ---- static-batch baseline: groups of n_slots, everyone waits for the
    # group's longest budget (what a naive batched `generate` loop does) -----
    def run_static():
        for i in range(0, cfg.requests, cfg.n_slots):
            group = list(range(i, min(i + cfg.n_slots, cfg.requests)))
            n_max = int(max(budgets[g] for g in group))
            width = int(max(lengths[g] for g in group))
            batch = np.zeros((len(group), width), np.int32)
            for row, g in enumerate(group):
                batch[row, width - lengths[g]:] = prompts[g]  # left-pad
            # np.asarray forces execution — async dispatch would otherwise
            # let the timer stop before the device finishes
            np.asarray(model.generate(
                params, batch, n_max, temperature=cfg.temperature, seed=cfg.seed
            ))

    run_static()  # warmup: compile per-group shapes
    t0 = time.monotonic()
    run_static()
    static_s = time.monotonic() - t0
    static_useful = 0
    static_ticks = 0
    for i in range(0, cfg.requests, cfg.n_slots):
        group = list(range(i, min(i + cfg.n_slots, cfg.requests)))
        n_max = int(max(budgets[g] for g in group))
        # decode ticks per lane = n_max - 1 (the first token comes from
        # prefill, same as the batcher); wanted ticks per request likewise
        static_useful += sum(int(budgets[g]) - 1 for g in group)
        static_ticks += (n_max - 1) * cfg.n_slots

    static_util = static_useful / max(static_ticks, 1)
    if n_adapt:
        # adaptive ticks decode a data-dependent number of steps, so fixed
        # lane-capacity accounting doesn't apply — the dispatch count IS
        # the story (early exit means no tick over-decodes a retired slot)
        log.info(
            "continuous: %.2fs (%d scheduler steps, %d adaptive early-exit "
            "decode dispatches, %d plain)",
            cont_s, steps, n_adapt, n_plain,
        )
    else:
        util = useful_ticks / max(lane_capacity, 1)
        log.info(
            "continuous: %.2fs (%d scheduler steps, lane utilization %.0f%%, "
            "%d plain / %d turbo decode dispatches)",
            cont_s, steps, 100 * util, n_plain, n_turbo,
        )
    log.info(
        "static    : %.2fs (lane utilization %.0f%% — idle lanes wait for the "
        "group's longest request)", static_s, 100 * static_util,
    )
    log.info(
        "tokens/s: continuous %.1f vs static %.1f",
        total_tokens / cont_s, total_tokens / static_s,
    )
    log.info(
        "reading the numbers: static fuses each group's ENTIRE decode into one "
        "compiled scan (zero host round trips), so it wins offline wall-clock "
        "at toy scale; continuous batching wins lane UTILIZATION (above), "
        "online arrival (it starts serving immediately), and tail latency — "
        "use --adaptive K (early-exit device loop) or raise --quantum to "
        "amortize the per-tick round trip"
    )


if __name__ == "__main__":
    main()
