/* memcalls: count the memory-management system calls of a running
 * process and all its threads for a while, as `strace -f -c -e
 * trace=memory -p PID` would (neither strace nor perf is installed
 * where dragnet-tpu is measured).
 *
 *   memcalls PID SECONDS   ->  one JSON line on stdout, after SECONDS
 *                              or at SIGTERM / SIGINT, whichever is first
 *
 * Every thread is seized (PTRACE_SEIZE: no stop on attach, new threads
 * follow by PTRACE_O_TRACECLONE) and run from system call to system
 * call; each entry is counted by its number.  The traced process runs
 * several times slower meanwhile: the counts are what is read, never a
 * time.  When the tracer exits the kernel detaches every thread. */
#define _GNU_SOURCE
#include <dirent.h>
#include <errno.h>
#include <signal.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>
#include <sys/ptrace.h>
#include <sys/syscall.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <linux/ptrace.h>

static const struct { long nr; const char *name; } WATCHED[] = {
    {SYS_mmap, "mmap"}, {SYS_munmap, "munmap"}, {SYS_madvise, "madvise"},
    {SYS_brk, "brk"}, {SYS_mprotect, "mprotect"}, {SYS_mremap, "mremap"},
};
#define NWATCHED (sizeof(WATCHED) / sizeof(WATCHED[0]))
#define NSIZES 20

static volatile sig_atomic_t stopped;

static void on_signal(int sig) {
    (void)sig;
    stopped = 1;
}

static double now(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return ts.tv_sec + ts.tv_nsec * 1e-9;
}

int main(int argc, char **argv) {
    if (argc != 3) {
        fprintf(stderr, "usage: memcalls PID SECONDS\n");
        return 2;
    }
    pid_t pid = (pid_t)atol(argv[1]);
    double seconds = atof(argv[2]);
    long counts[NWATCHED] = {0}, calls = 0, threads = 0;
    /* mmap and munmap by the length asked for, in powers of two from
     * 4 KiB (slot 0: up to 4 KiB; slot k: up to 4 KiB << k) */
    long by_size[2][NSIZES] = {{0}};
    const long opts = PTRACE_O_TRACESYSGOOD | PTRACE_O_TRACECLONE;

    char path[64];
    snprintf(path, sizeof path, "/proc/%d/task", (int)pid);
    DIR *d = opendir(path);
    if (!d) { perror(path); return 1; }
    struct dirent *e;
    while ((e = readdir(d)) != NULL) {
        pid_t tid = (pid_t)atol(e->d_name);
        if (tid <= 0) continue;
        if (ptrace(PTRACE_SEIZE, tid, 0, opts) != 0) {
            if (errno == ESRCH) continue;      /* it has just exited */
            perror("PTRACE_SEIZE");
            return 1;
        }
        ptrace(PTRACE_INTERRUPT, tid, 0, 0);   /* so that it can be resumed
                                                  to its next system call */
        threads++;
    }
    closedir(d);

    signal(SIGTERM, on_signal);
    signal(SIGINT, on_signal);
    double t0 = now();
    while (!stopped && now() - t0 < seconds) {
        int status;
        pid_t tid = waitpid(-1, &status, __WALL | WNOHANG);
        if (tid == 0) {
            struct timespec nap = {0, 200000};
            nanosleep(&nap, NULL);
            continue;
        }
        if (tid < 0) break;                    /* every thread has gone */
        if (!WIFSTOPPED(status)) continue;     /* a thread's exit */
        int sig = WSTOPSIG(status), event = status >> 16;
        long inject = 0;
        if (sig == (SIGTRAP | 0x80)) {
            struct ptrace_syscall_info info;
            if (ptrace(PTRACE_GET_SYSCALL_INFO, tid, sizeof info, &info) > 0
                    && info.op == PTRACE_SYSCALL_INFO_ENTRY) {
                calls++;
                for (size_t i = 0; i < NWATCHED; i++)
                    if ((long)info.entry.nr == WATCHED[i].nr) counts[i]++;
                if (info.entry.nr == SYS_mmap || info.entry.nr == SYS_munmap) {
                    int k = 0;
                    while (k < NSIZES - 1
                           && info.entry.args[1] > (4096ULL << k)) k++;
                    by_size[info.entry.nr == SYS_munmap][k]++;
                }
            }
        } else if (event == PTRACE_EVENT_CLONE) {
            threads++;
        } else if (event == PTRACE_EVENT_STOP) {
            if (sig == SIGSTOP || sig == SIGTSTP || sig == SIGTTIN
                    || sig == SIGTTOU) {       /* group stop: let it be */
                ptrace(PTRACE_LISTEN, tid, 0, 0);
                continue;
            }                                   /* else ours, or a new thread */
        } else if (event == 0) {
            inject = sig;                       /* a signal on its way in */
        }
        ptrace(PTRACE_SYSCALL, tid, 0, inject);
    }
    printf("{\"pid\": %d, \"seconds\": %.3f, \"threads_seen\": %ld, "
           "\"syscalls\": %ld", (int)pid, now() - t0, threads, calls);
    for (size_t i = 0; i < NWATCHED; i++)
        printf(", \"%s\": %ld", WATCHED[i].name, counts[i]);
    for (int m = 0; m < 2; m++) {
        printf(", \"%s_by_log2_4k\": [", m ? "munmap" : "mmap");
        for (int k = 0; k < NSIZES; k++)
            printf("%s%ld", k ? ", " : "", by_size[m][k]);
        printf("]");
    }
    printf("}\n");
    return 0;
}
