"""The benchmark's own library: file lookup, peaks, counts, trace
reduction and the result line.  Nothing here is imported by the
program under test."""
