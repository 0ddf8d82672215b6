"""Facade (``api/session.py``, ``api/backends.py``): mean wall time of a
window step's ``session.search`` call, as the service measures it
(``SearchRequest.service_s``), in milliseconds.  Host clock."""


def read(ctx):
    walls = [s.service_s for s in ctx.window.steps if s.service_s is not None]
    return sum(walls) / len(walls) * 1e3 if walls else None
