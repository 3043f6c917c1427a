"""Readers of the program's set-up ledger
(``stochastic_gradient_push_tpu/telemetry/setup_ledger.py``): every program
JAX built in this process before the first train step (traced, lowered,
compiled or loaded from the cache) and the program's own set-up phases, as
intervals on ``time.time()`` — the clock ``run.py`` counts ``setup_s`` on.
Every total is a union of intervals up to the end of the first train step's
build; what the comparison builds after the window comes later and is left
out.  Nothing where the program has no ledger (a commit from before it),
nobody armed it, or no train step was built under a name it knows."""


def _summary():
    try:
        from stochastic_gradient_push_tpu.telemetry import setup_ledger
    except ImportError:
        return None
    ledger = setup_ledger.LEDGER
    if not ledger.armed or ledger.cut is None:
        return None
    return ledger.summary()


def total(reading):
    """The summary's entry ``params.key``: ``trace_lower_s``,
    ``compile_s`` (backend intervals of the programs the cache did not
    give), ``cache_load_s`` (of those it gave), ``cache_misses`` (programs
    it should have held and did not: an entry was written)."""
    s = _summary()
    return None if s is None else float(s[reading.params["key"]])


def programs(reading):
    """Programs built up to and including the first train step; the
    harness's "programs built in set-up" counts the same events.  Prints
    the ledger's rows, so a traced run's log says where set-up went."""
    s = _summary()
    if s is None:
        return None
    for r in s["rows"]:
        print(f"{reading.cell.name}: set-up built {r['fun_name']}: trace "
              f"{r['trace_s']:.3f} lower {r['lower_s']:.3f} backend "
              f"{r['backend_s']:.3f} s, {r['cache']}"
              + (f" (build {r['build']})" if r["build"] > 1 else ""))
    print(f"{reading.cell.name}: set-up phases (ledger) {s['phases_s']}; "
          f"covered {s['covered_s']:.3f} s, two kinds of JAX's intervals "
          f"share {s['overlap_s']:.3f} s, under a phase and in no build "
          f"{s['phases_outside_builds_s']:.3f} s; built after set-up: "
          f"{[r['fun_name'] for r in s['later_rows']]}")
    return float(s["programs"])


def step_program_s(reading):
    """The first train step's row alone: its trace, its lowering and its
    compile or load."""
    s = _summary()
    return None if s is None else float(s["step_program"]["seconds"])


def unaccounted_s(reading):
    """This run's ``setup_s`` less the union of every interval the ledger
    holds up to the cut, JAX's and the program's phases alike: the
    interpreter's start, imports, the chip's start-up, running the init and
    batch programs, the harness's warm-up steps."""
    s = _summary()
    setup_s = reading.values.get("setup_s")
    if s is None or setup_s is None:
        return None
    return float(setup_s - s["covered_s"])
