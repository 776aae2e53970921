"""The on-chip benchmark of the concurrent graph-query engine.

`run_cell.py` is the entry point.  Everything that defines a cell is data
or a small module found by name: `configs/<config>.json`,
`traffic/<mix>.json` (whose `kind` names a driver in `drivers/<kind>.py`),
`algorithms/<algorithm>.py` (the job, its reference, its control and the
numbers compared) and one reader per metric in `metrics/<metric>.py`.
The rest of this package is the yardstick: the Graph500 generator, the
arrival generator, the plain references, the kernel's work and bytes, the
trace reduction and the comparison that decides `correct`.
"""
