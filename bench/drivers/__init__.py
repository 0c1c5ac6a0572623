"""How a cell runs, one module per traffic kind: ``drivers/<kind>.py``
generates the inputs of a mix of that kind from the seed, runs the cell and
gives ``calibrate.py`` its readings. ``harness.load_driver`` finds it by
the mix's ``kind``."""
