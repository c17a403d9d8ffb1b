"""The chip benchmark's yardstick: cell lookup, data made on the device,
plain references, the comparison that decides ``correct``, the trace
reduction and the table of peaks.

Everything that belongs to one configuration, traffic mix, driver, metric or
kernel count sits in a file of its own under ``benchmarks/chip/`` and is
found by name (:mod:`chipbench.spec`), so a new cell is new files only.
"""
