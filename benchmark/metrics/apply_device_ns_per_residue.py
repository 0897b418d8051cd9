"""Pass C's device time per residue it rewrites: the programs matching
the file's ``patterns`` over ``bqsr.apply.residues``, the summed length
of the reads whose qualities pass C rewrites (``per_residue.read``)."""

from per_residue import read  # noqa: F401
