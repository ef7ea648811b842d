"""Drivers: one module per kind of work a cell's window repeats.

A driver module defines ``Session(ctx)``: its constructor is the set-up
(inputs from the seed, the program's objects, warm-up of every shape the
window uses); ``step()`` does one unit of the window's work and returns
the run-rounds it completed; ``finish()`` drops the program's state once
the window has closed; ``check()`` compares what the timed path produced
with the plain reference and returns ``[(name, value, limit), ...]``;
``counts()`` gives the operations and bytes of the window's work for the
per-layer readers. The traffic file's ``driver`` key names the module.
"""
