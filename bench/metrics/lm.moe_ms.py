"""Device ms a step of the step program's ops under the ``moe`` scopes
(route, dispatch, experts, shared, combine), forward and backward
(``harness/lm_scopes.py``), averaged over chips."""
from harness import lm_scopes


def read(rec):
    scoped = (rec.get("lm") or {}).get("scopes")
    return lm_scopes.ms_per_step(scoped, "moe/") if scoped else None
