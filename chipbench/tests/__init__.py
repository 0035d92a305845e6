"""CPU tests of the chip benchmark at tiny sizes."""
