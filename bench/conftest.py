"""Configurations newer than the tables of the benchmark's CPU tests.

``tests/test_faults.py`` cuts every configuration of ``BENCHMARK.json`` to
the scale its ``SMALL`` table gives, by name.  A configuration that runs
the graph of one already listed takes that one's scale, so the fault suite
keeps running over the whole benchmark.
"""
SAME_GRAPH = {"gap-kron-15-pr": "gap-kron-15"}


def pytest_collection_modifyitems(items):
    for module in {getattr(item, "module", None) for item in items}:
        small = getattr(module, "SMALL", None)
        if isinstance(small, dict):
            for name, graph in SAME_GRAPH.items():
                small.setdefault(name, small[graph])
