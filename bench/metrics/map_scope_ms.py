"""Device ms per job of the timed job's ``mr.map`` scope: the union of the
intervals of its ops in the traced window, over the jobs run, averaged
over the chips used."""


def read(r):
    return r.scopes.get("map")
