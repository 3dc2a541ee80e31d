"""Scheduler: 85th percentile of admission to first token, as the scheduler
stamps them (``Request.t_admit``, ``Request.t_first``), over the requests
due in the window that got a first token: the part of TTFT after the
queue."""
from bench import readers


def read(record):
    ms = [(r.req.t_first - r.req.t_admit) * 1e3 for r in readers.win(record).recs
          if r.counted and r.req.t_first is not None and r.req.t_admit is not None]
    return readers.percentile(ms, 85)
