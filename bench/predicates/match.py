"""MATCH on every attribute: a row meets the predicate when each of its
attributes equals the query's.

A predicate file gives the same semantics twice: ``meets`` in NumPy for the
plain reference, which imports nothing of the program, and ``program`` as
the program's own predicate objects for the requests the window sends.
"""
import numpy as np


def meets(row_attrs: np.ndarray, query_attrs: np.ndarray) -> np.ndarray:
    """bool over rows: ``row_attrs`` (..., L) against ``query_attrs``
    broadcast to it."""
    return (row_attrs == query_attrs).all(-1)


def program(query_attrs: np.ndarray) -> list:
    """The program's predicate list for one query's attribute row."""
    from repro.api import MATCH

    return [MATCH(int(v)) for v in query_attrs]
