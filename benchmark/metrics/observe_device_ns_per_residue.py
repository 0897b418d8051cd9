"""Observe's device time per residue it admits: the programs matching
the file's ``patterns`` over ``bqsr.observe.residues``, the summed
length of the reads the observe filter admits (``per_residue.read``)."""

from per_residue import read  # noqa: F401
