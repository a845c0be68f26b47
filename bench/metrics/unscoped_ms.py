"""Device ms per job of the timed job's ops that no ``mr.<phase>`` scope
reaches (on a mesh, ``sharded``'s gather of the output onto every chip),
averaged over the chips used; 0 where every op of the job is scoped."""

from bench.scopes import UNSCOPED


def read(r):
    return r.scopes.get(UNSCOPED, 0.0) if r.scopes else None
