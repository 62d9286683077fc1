"""Mean host-clock time of the window's sparse steps (the trainer's own
per-step clock: dispatch to the host read of the step's metrics)."""


def read(r):
    s = r.get("step_s", {}).get("sparse") or []
    return 1e3 * sum(s) / len(s) if s else None
