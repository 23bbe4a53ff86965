"""The benchmark's own library: cell resolution, trace reduction, HLO
collective counting, model FLOPs and kernel bytes, and the plain references
that decide ``correct``.  Nothing here is imported by the program under
test, and nothing here imports the program except the drivers."""
