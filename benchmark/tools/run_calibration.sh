#!/bin/bash
# The readings a cell's limits are set from, in one chip call: a dozen seeds of
# the program (the int8 reference beside every second one, a look at routing on
# the first two); the program's own int8 path on three more seeds, in a process
# of its own; the planted fault on one. Run by hand through the chip tool:
#   bash benchmark/tools/run_calibration.sh <workload>
W=$1
mkdir -p chiprun_out
C="python benchmark/tools/calibrate.py --workload $W --seconds 3 --set traffic.check_prompts=16"
$C --seeds 4101,2147487102,4103,3000004104,4105,4106,4107,2147487108,4109,4110,4111,4112 \
   --control-every 2 --routing-look 2 2>chiprun_out/cal_err.$W.txt | cut -c1-700
$C --seeds 4301,2147487302,4303 --program-int8 3 --control-every 0 \
   2>>chiprun_out/cal_err.$W.txt | cut -c1-900
$C --seeds 4201 --fault alter_answer --control-every 0 2>>chiprun_out/cal_err.$W.txt | cut -c1-500
grep -E "Error|Traceback" chiprun_out/cal_err.$W.txt | head -20
tail -c 1500 chiprun_out/cal_err.$W.txt
