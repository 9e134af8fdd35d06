"""Closed loop over rounds: one client issues its next request only when
the answer of the previous one is on the host, each alone through
``system.issue``, and each round asks every request of the round once, in
its order.  It starts the first round at once and begins no round after
``seconds``, so the window holds whole rounds, the same work in every run,
and ends with the answer of the last request of the last round begun
inside it."""
import time

PARAMS = ()             # it reads no traffic parameter of its own


def run(system, requests, seconds, span, keep, traffic):
    """Drive ``system.issue(request)`` and ``system.fetch(handle)`` over
    whole rounds of ``requests`` for at least ``seconds``.

    ``span(name)`` opens a named host span; ``keep(request, answer)`` sees
    every answer as it arrives.  Returns ``(window_s, answered)``, one dict
    per answer: its ``request``, its ``seconds`` and the handle's ``stats``
    (or None)."""
    answered = []
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t = t_start
    while t < deadline:
        for req in requests:
            with span("query"):
                handle = system.issue(req)
            with span("answer_to_host"):
                answer = system.fetch(handle)
            t_end = time.perf_counter()
            with span("between_queries"):
                answered.append({"request": req, "seconds": t_end - t,
                                 "stats": getattr(handle, "stats", None)})
                keep(req, answer)
            t = t_end
    return t - t_start, answered
