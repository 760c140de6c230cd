"""Host time of the state build on the chip (``from_edges``, or
``sharded_from_edges`` on a mesh), compile or cache load included,
ending when the state is ready, in s."""


def read(f):
    return f["counters"].get("state_build_s")
