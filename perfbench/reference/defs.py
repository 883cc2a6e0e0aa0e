"""Copied from the system under test (engine/defs.py) so that the plain
reference imports nothing of the program."""

# --- Event kinds (eq_kind) ---
EV_NULL = 0        # empty queue slot
EV_APP = 1         # app wake: payload AUX = reason, SEQ = socket (or -1)
EV_PKT = 2         # packet arrival at this host's NIC; payload = packet
EV_NIC_TX = 3      # NIC transmit becomes free; pull next packet
EV_TCP_TIMER = 4   # TCP retransmission timer; payload SEQ=socket, ACK=generation
EV_TCP_CLOSE = 5   # TCP close/TIME_WAIT teardown timer; payload SEQ=socket
N_EVENT_KINDS = 6

# --- App wake reasons (in EV_APP payload AUX word) ---
WAKE_START = 0       # process start (reference: _process_runStartTask)
WAKE_TIMER = 1       # app-requested timer
WAKE_SOCKET = 2      # socket readable/writable/established/closed
WAKE_CONNECTED = 3   # connection established (TCP handshake done)
WAKE_EOF = 4         # peer FIN: stream finished
WAKE_ACCEPT = 5      # listener accepted a new child connection
WAKE_SENT = 6        # all written bytes acked (send complete)

# --- Per-host stat slots (stats[H, N_STATS] int64) ---
ST_EVENTS = 0          # events executed
ST_PKTS_SENT = 1       # packets handed to the wire (incl. retransmits)
ST_PKTS_RECV = 2       # packets arriving at NIC
ST_PKTS_DROP_NET = 3   # dropped by topology reliability roll
ST_PKTS_DROP_BUF = 4   # dropped: receiver NIC input buffer full
ST_PKTS_DROP_Q = 5     # dropped: destination event queue overflow
#                        (exchange belt-and-braces only since round 3 —
#                        arrivals that cannot merge DEFER at the source
#                        instead; a nonzero value here is an engine bug)
ST_BYTES_SENT = 6      # payload bytes sent (first transmission)
ST_BYTES_RECV = 7      # payload bytes received in order (delivered to app)
ST_RETRANSMIT = 8      # TCP segments retransmitted
ST_OUTBOX_DROP = 9     # dropped: outbox overflow (window emit budget)
ST_EQ_FULL_LOCAL = 10  # dropped local pushes: own queue full
ST_SOCK_FAIL = 11      # socket allocation failures
ST_APP_DONE = 12       # app reached terminal state (end node)
ST_XFER_DONE = 13      # app-level transfers completed
ST_RTT_SUM_US = 14     # accumulated app RTT measurements (microseconds)
ST_RTT_COUNT = 15      # number of RTT samples
ST_TXQ_DROP = 16       # dropped: NIC transmit ring full (sndbuf overflow)
ST_TGEN_DROP = 17      # tgen walk forks lost to cursor-stack overflow
ST_CHAIN_SHORT = 18    # socks circuits shortened: relay had no pool to
#                        extend a hops>0 CONNECT (config mismatch)
ST_SACK_RENEGE = 19    # receiver OOO scoreboard overflow discarded a
#                        range possibly already advertised (stall ends
#                        at the RTO; see net/sack.py insert_counted)
ST_TGEN_ABORT = 20     # tgen transfers aborted by timeout/stallout
#                        (shd-tgen-transfer.c:918-961 semantics)
ST_DEFER_FANIN = 21    # packets deferred to the next window at the
#                        SOURCE because the destination's per-window
#                        intake (incap or queue headroom) was spent —
#                        exact carry, arrival times unchanged; counted
#                        per window deferred (a packet carried 3
#                        windows counts 3). The engine-artifact
#                        replacement for what used to be a drop: the
#                        only modeled-semantics drop point is the NIC
#                        input buffer (ST_PKTS_DROP_BUF,
#                        shd-network-interface.c:288-311)
ST_DEFER_A2A = 22      # packets deferred at the source because the
#                        sharded exchange's per-(src,dst)-shard bucket
#                        was full (parallel.shard; raise a2acap if this
#                        grows — deferral is exact but delays delivery
#                        processing by a window)
ST_FAULTS = 23         # injected fault events applied to this host
#                        (engine.faults: host kill/restart count at the
#                        faulted host; the RSTs a kill sends toward
#                        peers ride the normal EV_PKT path and are NOT
#                        separately counted here)
N_STATS = 24
