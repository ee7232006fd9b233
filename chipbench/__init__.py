"""Chip benchmark of GNN node-query serving (see README.md)."""
