"""Traffic loops, one file each, found by the traffic file's ``loop``."""
