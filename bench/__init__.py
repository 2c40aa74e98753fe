"""The benchmark of the emulator on the chip: ``python3 bench/run.py``."""
