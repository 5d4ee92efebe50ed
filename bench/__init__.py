"""Chip benchmark of the AI+R-tree's served path (``python -m bench.run``)."""
