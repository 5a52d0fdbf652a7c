// Native host-side runtime hooks (XLA FFI custom calls, CPU backend).
//
// TPU-native equivalent of the runtime responsibilities of the reference's
// Cython bridge (ref mpi4jax/_src/xla_bridge/mpi_xla_bridge.pyx): on TPU the
// collectives themselves are compiler-emitted HLO with no host hook needed,
// but the *runtime* services the bridge provided still need a native home
// (SURVEY.md §7 step 7):
//
//   - per-op begin/end logging in the reference's format
//     ("r{rank} | {id} | MPI_X ..." / "... done ({elapsed}s)",
//     ref mpi_xla_bridge.pyx:47-60, 100-112), with wall-clock op latency
//     measured across the collective on the host;
//   - fail-fast abort: a data-dependent guard that kills the process when a
//     runtime predicate fires (the MPI_Abort-on-error semantics of
//     ref mpi_xla_bridge.pyx:67-91);
//   - collective watchdog (mpi4jax_tpu/resilience/watchdog.py): an arm/disarm
//     registry of in-flight collectives plus a C++ monitor thread that dumps
//     per-rank diagnostics and aborts when one exceeds its timeout.  The
//     registry lives here (not Python) so the timeout fires even when every
//     Python thread is wedged behind the GIL.
//
// Build: see csrc/CMakeLists.txt or `python -m mpi4jax_tpu.native build`.
// Loaded and registered from mpi4jax_tpu/native.py via ctypes + jax.ffi.

#include <chrono>
#include <cinttypes>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <deque>
#include <mutex>
#include <string>
#include <string_view>
#include <thread>
#include <unordered_map>

#include "xla/ffi/api/ffi.h"

namespace ffi = xla::ffi;

namespace {

double Now() {
  using clock = std::chrono::steady_clock;
  return std::chrono::duration<double>(clock::now().time_since_epoch()).count();
}

// (call_id, rank) -> FIFO of begin timestamps.  Call ids are unique per
// *trace site*, so a site inside lax.fori_loop fires once per iteration with
// the same id: the data dependencies order iteration N+1's begin after
// iteration N's collective, but not after N's end hook, so a plain map entry
// could be overwritten.  FIFO pairing matches each end with its own begin.
// Multiple devices run concurrently on the CPU backend, hence the mutex.
std::mutex mu;
std::unordered_map<std::string, std::deque<double>> begin_times;

ffi::Error OpBeginImpl(ffi::BufferR0<ffi::U32> rank,
                       ffi::Result<ffi::BufferR0<ffi::U32>> out,
                       std::string_view opname, std::string_view call_id,
                       std::string_view detail) {
  uint32_t r = rank.typed_data()[0];
  std::string key = std::string(call_id) + ":" + std::to_string(r);
  {
    std::lock_guard<std::mutex> lock(mu);
    begin_times[key].push_back(Now());
  }
  if (detail.empty()) {
    std::fprintf(stderr, "r%" PRIu32 " | %.*s | %.*s\n", r,
                 (int)call_id.size(), call_id.data(), (int)opname.size(),
                 opname.data());
  } else {
    std::fprintf(stderr, "r%" PRIu32 " | %.*s | %.*s: %.*s\n", r,
                 (int)call_id.size(), call_id.data(), (int)opname.size(),
                 opname.data(), (int)detail.size(), detail.data());
  }
  out->typed_data()[0] = r;
  return ffi::Error::Success();
}

ffi::Error OpEndImpl(ffi::BufferR0<ffi::U32> rank,
                     ffi::Result<ffi::BufferR0<ffi::U32>> out,
                     std::string_view opname, std::string_view call_id) {
  uint32_t r = rank.typed_data()[0];
  std::string key = std::string(call_id) + ":" + std::to_string(r);
  double elapsed = 0.0;
  {
    std::lock_guard<std::mutex> lock(mu);
    auto it = begin_times.find(key);
    if (it != begin_times.end() && !it->second.empty()) {
      elapsed = Now() - it->second.front();
      it->second.pop_front();
      if (it->second.empty()) begin_times.erase(it);
    }
  }
  // matches the reference's completion line (mpi_xla_bridge.pyx:108-112);
  // "code 0" kept for format parity — XLA collectives cannot return nonzero
  std::fprintf(stderr, "r%" PRIu32 " | %.*s | %.*s done with code 0 (%.2es)\n",
               r, (int)call_id.size(), call_id.data(), (int)opname.size(),
               opname.data(), elapsed);
  out->typed_data()[0] = r;
  return ffi::Error::Success();
}

ffi::Error AbortIfImpl(ffi::BufferR0<ffi::U32> pred,
                       ffi::BufferR0<ffi::U32> rank,
                       ffi::Result<ffi::BufferR0<ffi::U32>> out,
                       std::string_view message) {
  uint32_t p = pred.typed_data()[0];
  uint32_t r = rank.typed_data()[0];
  if (p != 0) {
    // fail-fast across the job, like MPI_Abort after an MPI error
    // (ref mpi_xla_bridge.pyx:67-91): print and kill the process group
    std::fprintf(stderr, "r%" PRIu32 " | FATAL: %.*s\n", r,
                 (int)message.size(), message.data());
    std::fflush(stderr);
    std::abort();
  }
  out->typed_data()[0] = p;
  return ffi::Error::Success();
}

// ---------------------------------------------------------------------------
// collective watchdog (resilience/watchdog.py's native backend)
// ---------------------------------------------------------------------------

struct WatchdogEntry {
  uint32_t rank;
  std::string opname;
  std::string call_id;
  std::string axes;
  double start;
  double timeout;
};

// Same FIFO-per-(call_id, rank) aliasing story as begin_times above: a trace
// site inside lax.fori_loop re-arms with the same call id before the prior
// iteration's disarm is ordered, so a plain map entry could be clobbered.
std::mutex wd_mu;
std::unordered_map<std::string, std::deque<WatchdogEntry>> wd_inflight;
bool wd_thread_running = false;

void WatchdogDump(const WatchdogEntry& expired, double now) {
  // called with wd_mu held; never returns
  for (const auto& kv : wd_inflight) {
    for (const auto& e : kv.second) {
      std::fprintf(stderr,
                   "r%" PRIu32 " | WATCHDOG | in-flight: %s (call %s, "
                   "axes=%s, elapsed %.2fs)\n",
                   e.rank, e.opname.c_str(), e.call_id.c_str(),
                   e.axes.c_str(), now - e.start);
    }
  }
  std::fprintf(stderr,
               "r%" PRIu32 " | FATAL: collective watchdog: %s exceeded "
               "%gs (call %s, axes=%s)\n",
               expired.rank, expired.opname.c_str(), expired.timeout,
               expired.call_id.c_str(), expired.axes.c_str());
  std::fflush(stderr);
  std::abort();
}

void WatchdogLoop() {
  for (;;) {
    std::this_thread::sleep_for(std::chrono::milliseconds(100));
    double now = Now();
    std::lock_guard<std::mutex> lock(wd_mu);
    for (const auto& kv : wd_inflight) {
      for (const auto& e : kv.second) {
        if (now - e.start > e.timeout) WatchdogDump(e, now);
      }
    }
  }
}

ffi::Error WatchdogArmImpl(ffi::BufferR0<ffi::U32> rank,
                           ffi::Result<ffi::BufferR0<ffi::U32>> out,
                           std::string_view opname, std::string_view call_id,
                           std::string_view axes, double timeout) {
  uint32_t r = rank.typed_data()[0];
  std::string key = std::string(call_id) + ":" + std::to_string(r);
  {
    std::lock_guard<std::mutex> lock(wd_mu);
    wd_inflight[key].push_back(WatchdogEntry{
        r, std::string(opname), std::string(call_id), std::string(axes),
        Now(), timeout});
    if (!wd_thread_running) {
      std::thread(WatchdogLoop).detach();
      wd_thread_running = true;
    }
  }
  out->typed_data()[0] = r;
  return ffi::Error::Success();
}

// `dep` is an element of the collective's first output: an operand only so
// that the call cannot be scheduled before the collective (and so before its
// arm, whose output the collective's inputs are computed from).
ffi::Error WatchdogDisarmImpl(ffi::BufferR0<ffi::U32> rank, ffi::AnyBuffer dep,
                              ffi::Result<ffi::BufferR0<ffi::U32>> out,
                              std::string_view call_id) {
  (void)dep;
  uint32_t r = rank.typed_data()[0];
  std::string key = std::string(call_id) + ":" + std::to_string(r);
  {
    std::lock_guard<std::mutex> lock(wd_mu);
    auto it = wd_inflight.find(key);
    if (it != wd_inflight.end() && !it->second.empty()) {
      it->second.pop_front();
      if (it->second.empty()) wd_inflight.erase(it);
    }
  }
  out->typed_data()[0] = r;
  return ffi::Error::Success();
}

ffi::Error WallclockImpl(ffi::BufferR0<ffi::U32> token,
                         ffi::Result<ffi::BufferR0<ffi::F64>> out) {
  (void)token;
  // Seconds since this library's first wallclock read, not since boot:
  // callers may downcast to f32 (x64-disabled JAX), where a since-boot
  // value has millisecond ULP. Differences are what is meaningful.
  static const double base = Now();
  out->typed_data()[0] = Now() - base;
  return ffi::Error::Success();
}

}  // namespace

XLA_FFI_DEFINE_HANDLER_SYMBOL(MpxOpBegin, OpBeginImpl,
                              ffi::Ffi::Bind()
                                  .Arg<ffi::BufferR0<ffi::U32>>()
                                  .Ret<ffi::BufferR0<ffi::U32>>()
                                  .Attr<std::string_view>("opname")
                                  .Attr<std::string_view>("call_id")
                                  .Attr<std::string_view>("detail"));

XLA_FFI_DEFINE_HANDLER_SYMBOL(MpxOpEnd, OpEndImpl,
                              ffi::Ffi::Bind()
                                  .Arg<ffi::BufferR0<ffi::U32>>()
                                  .Ret<ffi::BufferR0<ffi::U32>>()
                                  .Attr<std::string_view>("opname")
                                  .Attr<std::string_view>("call_id"));

XLA_FFI_DEFINE_HANDLER_SYMBOL(MpxAbortIf, AbortIfImpl,
                              ffi::Ffi::Bind()
                                  .Arg<ffi::BufferR0<ffi::U32>>()
                                  .Arg<ffi::BufferR0<ffi::U32>>()
                                  .Ret<ffi::BufferR0<ffi::U32>>()
                                  .Attr<std::string_view>("message"));

XLA_FFI_DEFINE_HANDLER_SYMBOL(MpxWallclock, WallclockImpl,
                              ffi::Ffi::Bind()
                                  .Arg<ffi::BufferR0<ffi::U32>>()
                                  .Ret<ffi::BufferR0<ffi::F64>>());

XLA_FFI_DEFINE_HANDLER_SYMBOL(MpxWatchdogArm, WatchdogArmImpl,
                              ffi::Ffi::Bind()
                                  .Arg<ffi::BufferR0<ffi::U32>>()
                                  .Ret<ffi::BufferR0<ffi::U32>>()
                                  .Attr<std::string_view>("opname")
                                  .Attr<std::string_view>("call_id")
                                  .Attr<std::string_view>("axes")
                                  .Attr<double>("timeout"));

XLA_FFI_DEFINE_HANDLER_SYMBOL(MpxWatchdogDisarm, WatchdogDisarmImpl,
                              ffi::Ffi::Bind()
                                  .Arg<ffi::BufferR0<ffi::U32>>()
                                  .Arg<ffi::AnyBuffer>()
                                  .Ret<ffi::BufferR0<ffi::U32>>()
                                  .Attr<std::string_view>("call_id"));
