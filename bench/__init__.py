"""The cell-based benchmark: ``python bench/run.py --workload <name> ...``."""
