"""Chip benchmark of CP-ALS on FROSTT-sized sparse tensors: `python bench/run.py`."""
